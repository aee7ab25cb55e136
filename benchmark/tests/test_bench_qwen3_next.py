"""The Qwen3-Next configuration's own pieces of the yardstick: its FLOP, delta
rule and share counts against numbers worked out by hand, each new metric's
pattern against instruction text at the cell's shapes (copied from the chip's
trace of the cell, PR 34) on a hand-made event list, the reference kept
identical to the tests' copy, the configuration against the catalog's
numbers, and `reference_check_qwen3_next.py --tiny`. (`run.py --tiny` of the
cell, both ways, is `test_bench_run_tiny.py`'s, which runs every file under
`workloads/`.)"""

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
CELL = "qwen3_next_80b_a3b.bs1"


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def counts_module():
    return importlib.import_module(
        "flops." + load("configs", "qwen3_next_80b_a3b.json")["flops"])


def flops(seq_len=4096, **over):
    c = load("configs", "qwen3_next_80b_a3b.json")
    return counts_module().flops_per_example(
        **dict(c["build_args"], seq_len=seq_len, **over))


def test_qwen3_next_flops_by_hand():
    # multiply-adds a token. A delta-rule layer: W_qkvz 2048 x (16 x (128 +
    # 128 + 256 + 256) = 12288), W_ba 2048 x 64, W_out 4096 x 2048
    gdn_proj = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    assert gdn_proj == 33_685_504                       # "33.7 M"
    # the rule at chunk 64, 32 value heads of 128 x 128: 3 x 128 x 128 (w S,
    # k^T v', q S) + 64 x (128 + 128 + 128) ((beta k) k^T, q k^T, scores v')
    # + 64 x (128 + 128) / 2 (the triangular solve of 256 columns)
    rule = 32 * (3 * 128 * 128 + 64 * 384 + 64 * 128)
    assert rule == 2_621_440
    # the attention layer: W_q 2048 x (16 x 2 x 256), W_k and W_v 2048 x 512,
    # W_o 4096 x 2048; attention, the causal half: T x 16 x 256
    attn_proj = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    assert attn_proj == 27_262_976                      # "27.3 M"
    attention = 4096 * 16 * 256
    # every layer's experts: the router at 512, the shared expert and its
    # gate, the routed experts at 10 x 32 / 512 = 0.625 of one a token
    router, shared = 2048 * 512, 3 * 2048 * 512 + 2048
    routed = 10 * 32 * 3 * 2048 * 512 // 512
    assert routed == 1_966_080
    head = 2048 * 18992
    per_token = 3 * (gdn_proj + rule) + attn_proj + attention \
        + 4 * (router + shared + routed) + head
    got = flops()
    assert got["forward"] == 2 * 4096 * per_token
    assert got["forward_backward"] == 3 * got["forward"]
    assert got["forward_backward"] / 1e12 == pytest.approx(5.32, abs=0.005)
    assert got["forward_backward"] / 4096 / 1e9 == pytest.approx(1.30,
                                                                abs=0.005)
    assert got["positions_per_example"] == 4096
    assert got["layers"] == {"linear_attention": 3, "full_attention": 1}
    assert got["gdn_layers_share"] == pytest.approx(0.503, abs=1e-3)
    assert got["head_share"] == pytest.approx(0.180, abs=1e-3)
    # all 512 experts held: the routed part is ten whole experts a token
    whole = flops(experts_held=None)
    assert whole["multiply_adds_per_token"]["routed_experts"] == \
        10 * 3 * 2048 * 512
    assert counts_module().layer_kinds(48, 4) == (36, 12)


def test_gdn_counts_by_hand():
    got = flops()
    # three layers, forward and twice that backward, 2 FLOPs a multiply-add
    assert got["gdn_flops"] == 3 * 3 * 2 * 4096 * 2_621_440
    assert got["gdn_flops"] == 193_273_528_320
    # forward: q, k [4096, 2048] and v [4096, 4096] read, o written;
    # backward: q, k, v, dO read, dq, dk, dv written; bf16
    values = (2 * 2048 + 2 * 4096) + (2 * 2048 + 2 * 4096) \
        + (2 * 2048 + 4096)
    assert got["gdn_bytes"] == 3 * 4096 * values * 2 == 805_306_368
    # the two bounds meet: 0.981 ms of products, 0.983 ms of traffic a step
    assert got["gdn_flops"] / 197e12 == pytest.approx(0.981e-3, rel=1e-3)
    assert got["gdn_bytes"] / 819e9 == pytest.approx(0.983e-3, rel=1e-3)


def test_share_expert_counts_by_hand():
    got = flops()
    rows = 4096 * 10 * 32 // 512
    assert rows == got["share_expert_rows"] == 2560     # 80 an expert
    # nine products a layer (gate, up, down: forward, input gradient, weight
    # gradient), four layers, each rows x 2048 x 512 multiply-adds
    assert got["share_expert_flops"] == 36 * 2 * 2560 * 2048 * 512
    assert got["share_expert_flops"] == 193_273_528_320
    # each reads or writes [2560, 2048] and [2560, 512] in bf16; the 32 held
    # experts' stack [32, 2048, 512] (67 MB) is NOT counted: XLA keeps it on
    # the chip, and with it counted the share read 119% on the chip
    one = (2560 * 2048 + 2560 * 512) * 2
    assert one == 13_107_200
    assert got["share_expert_bytes"] == 36 * one
    # the products bound it: 0.98 ms against 0.58 ms of traffic
    assert got["share_expert_flops"] / 197e12 == pytest.approx(0.981e-3,
                                                                rel=1e-3)
    assert 36 * one / 819e9 == pytest.approx(0.576e-3, rel=1e-3)
    # in the deployment an expert sees 16 times the rows: 40960 a layer
    deployed = counts_module().share_expert_counts(
        16 * 4096, 4, 2048, 512, 32, 10, 512)
    assert deployed["rows"] == 40960
    assert deployed["flops"] == 16 * got["share_expert_flops"]


OPS = tr.OPS_LINE
D0 = "/device:TPU:0"
# instruction texts of the cell's step as the chip's trace carried them (my
# chip runs, PR 34, calls 2 and 4: one of each kind, operand shapes and all;
# a long operand list cut at "...)"): the delta rule's ops, ops that are not
# its (among them the attention layer's, whose later results and operands
# hold `[4096,16]` and `[4096,16,256]`), the held experts' kernels and
# layout, attention at head 256
with open(os.path.join(BENCH, "tests", "qwen3_next_trace_names.json")) as f:
    NAMES = json.load(f)
MS = {"chunk_a": 0.5, "solve": 0.25, "cumsum": 0.125, "u_w": 1.0,
      "state": 2.0, "stacked": 1.0, "l2norm": 0.125,
      "chunked_v": 0.5, "decay": 0.25, "head_slice": 0.25,  # the rule: 6.0
      "while": 3.0,     # a span over its body's events: in no metric
      "conv": 3.0, "qkvz": 4.0, "head": 2.0,
      # first result or a later one shaped like the rule's, not the rule:
      # the l2-norms' per-head sums (the q-norm's statistics look alike),
      # the layer's slices of the convolution's output, the attention
      # layer's output gate, q-norm, its statistics and rotary
      "l2_sum": 0.125, "qk_slices": 0.125, "attn_gate": 0.5,
      "attn_qnorm": 0.25, "attn_stats": 0.25, "attn_rope": 0.25,
      "gmm": 0.75, "tgmm": 0.25,                           # 1.0
      "sort": 0.5, "gather_in": 0.25, "select_in": 0.125,
      "gather_out": 0.75, "weighted": 0.25, "slot_1d": 0.125,  # 2.0
      "swiglu": 0.5, "topk": 0.5,
      "fwd": 0.75, "dq": 1.0, "dkv": 1.25}                 # 3.0
RULE = ("chunk_a", "solve", "cumsum", "u_w", "state", "stacked", "l2norm",
        "chunked_v", "decay", "head_slice")
DISPATCH = ("sort", "gather_in", "select_in", "gather_out", "weighted",
            "slot_1d")


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


@pytest.mark.parametrize("name,found", [
    ("gdn_scan_ms.train", RULE),
    ("share_expert_matmul_ms.train", ("gmm", "tgmm")),
    ("share_dispatch_ms.train", DISPATCH),
    ("hybrid_attention_kernels_ms.train", ("fwd", "dq", "dkv"))])
def test_cell_pattern_finds_its_ops_and_no_others(name, found):
    pattern = re.compile(load("metrics", name + ".json")["args"]["pattern"])
    hit = {key for key, text in NAMES.items() if pattern.search(text)}
    assert hit == set(found)
    assert metric(name) == pytest.approx(sum(MS[k] for k in found))


def test_roofline_shares_on_a_hand_made_trace():
    # the rule needs 0.983 ms of traffic a step (0.981 ms of products); this
    # trace shows 6.0 ms: 16.4%, memory-bound by a hair
    assert metric("gdn_scan_roofline_pct.train") == \
        pytest.approx(100 * (805_306_368 / 819e9) / 6.0e-3)
    # the held experts' products need 0.981 ms (0.576 ms of traffic); this
    # trace shows 1.0 ms (two of 36 calls): 98.1%, compute-bound
    assert metric("share_expert_matmul_roofline_pct.train") == \
        pytest.approx(100 * (193_273_528_320 / 197e12) / 1.0e-3)


def test_patterns_hold_the_cells_shapes():
    """The delta rule's and the dispatch's ops are found by shapes written
    into the patterns: the cell's configuration has to have those shapes."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {"gdn_scan_ms.train", "gdn_scan_roofline_pct.train",
           "share_expert_matmul_ms.train",
           "share_expert_matmul_roofline_pct.train",
           "share_dispatch_ms.train", "hybrid_attention_kernels_ms.train",
           "moe_row_buffer_rows.train"}
    listed = {m["name"]: m for m in bench["per_layer"] if m["name"] in new}
    assert set(listed) == new
    for m in listed.values():
        assert m["workloads"] == [CELL] and \
            m["moves"] == "train_examples_per_s", m["name"]
    cell = load("workloads", CELL + ".json")
    args = dict(load("configs", cell["config"] + ".json")["build_args"],
                **load("traffic", cell["traffic"] + ".json")["build_args"])
    gdn = load("metrics", "gdn_scan_ms.train.json")["args"]["pattern"]
    assert (args["n_value_head"], args["key_dim"], args["value_dim"],
            args["n_key_head"], args["seq_len"]) == (32, 128, 128, 16, 4096)
    assert args["seq_len"] // 64 == 64              # chunks of 64 tokens
    for piece in ("32,64,", "(64|16),1,32", "32,128,128", "4096,(16|4)"):
        assert piece in gdn
    # the first result alone, its shape matched whole
    assert gdn.startswith("^%?(?!while)[\\w.\\-]+ = \\(?\\w+\\[(") \
        and gdn.endswith(")\\]")
    assert load("metrics", "gdn_scan_roofline_pct.train.json")["args"][
        "pattern"] == gdn
    dispatch = load("metrics", "share_dispatch_ms.train.json")["args"][
        "pattern"]
    assert str(args["seq_len"] * args["top_k"]
               + args["experts_held"] * 128) in dispatch   # the layout's rows
    assert str(args["seq_len"] * args["top_k"]) in dispatch
    assert str(args["d_model"]) in dispatch


def test_row_buffer_reader_on_a_hand_made_observatory(monkeypatch):
    def event(uid, detail):
        return types.SimpleNamespace(program_uid=uid, detail=detail)
    events = [event(3, {"version": 1, "grad_fanin_max": 0}),     # startup
              event(5, {"version": 2, "grad_fanin_max": 1,
                        "layer_kinds": {"linear_attention": 3,
                                        "full_attention": 1},
                        "moe_experts_routed": 512, "moe_experts_held": 32,
                        "moe_row_buffer_rows": 45056})]
    sys.path.insert(0, ROOT)
    from paddle_tpu import observe
    monkeypatch.setattr(observe.observatory(), "events", lambda: events)
    system = types.SimpleNamespace(main=types.SimpleNamespace(_uid=5))
    assert metric("moe_row_buffer_rows.train", {"system": system}) == 45056.0
    assert compile_detail.program_detail(events, 5, "moe_experts_held") == 32
    system.main._uid = 3        # a program older than the key: left out
    assert metric("moe_row_buffer_rows.train", {"system": system}) is None


def test_reference_copies_are_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "references", "qwen3_next_reference.py"),
        os.path.join(ROOT, "tests", "qwen3_next_reference.py"),
        shallow=False)


def test_config_holds_the_catalog_numbers_and_lists_its_three_cuts():
    c = load("configs", "qwen3_next_80b_a3b.json")
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-6,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False}
    for key, value in published.items():
        assert c[key] == value, key
    # the three cuts, each with what was published beside it
    assert (c["num_hidden_layers"], c["num_hidden_layers_published"]) == \
        (4, 48)
    assert (c["num_experts"], c["num_experts_published"]) == (32, 512)
    assert (c["vocab_size"], c["vocab_size_published"]) == (18992, 151936)
    assert c["vocab_size"] * 8 == c["vocab_size_published"]
    b = c["build_args"]
    assert (b["d_model"], b["n_head"], b["n_kv_head"], b["head_dim"],
            b["rotary_dim"], b["rope_theta"], b["n_key_head"],
            b["n_value_head"], b["key_dim"], b["value_dim"],
            b["conv_kernel"], b["n_expert"], b["top_k"], b["d_expert"],
            b["d_shared"], b["norm_topk_prob"], b["rms_eps"]) == \
        (2048, 16, 2, 256, 64, 1e7, 16, 32, 128, 128, 4, 512, 10, 512, 512,
         True, 1e-6)
    assert b["rotary_dim"] == c["partial_rotary_factor"] * c["head_dim"]
    assert (b["n_layer"], b["full_attention_interval"], b["experts_held"],
            b["first_expert"], b["vocab_size"]) == (4, 4, 32, 0, 18992)
    assert c["feed_ranges"] == {"tokens": [0, 18992], "labels": [0, 18992]}
    assert [r.split()[0] for r in c["reduced"]] == \
        ["num_hidden_layers", "num_experts", "vocab_size"]
    assert "16 chips share each layer" in c["deployment"]
    for key in ("multi-token prediction", "load-balancing loss",
                "initialisation", "optimizer", "labels"):
        assert len(c["assumed"][key]) > 40, key
    # no knob for the expert layer's rows: they are the worst case
    assert "row_buffer" not in b and "row_buffer" not in \
        c["tiny"]["build_args"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [e for e in json.load(f)["configs"]
                  if e["name"] == "qwen3_next_80b_a3b"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == c["source"]


def test_traffic_is_ouros_but_for_the_reference():
    old = load("traffic", "steady_b1_s4096_ouro.json")
    new = load("traffic", "steady_b1_s4096_qwen3_next.json")
    for key in ("generator", "batch", "build_args", "pool_batches", "feed",
                "in_flight", "warmup", "traced"):
        assert new[key] == old[key], key
    assert new["reference_check"]["reference"] == "qwen3_next_reference"
    assert new["reference_check"]["reference_args"] == \
        {"q_block": 512, "token_block": 64}
    assert len(new["reference_check"]["loss_atol_why"]) > 200
    cell = load("workloads", CELL + ".json")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("qwen3_next_80b_a3b", "steady_b1_s4096_qwen3_next", 1)


def test_reference_check_tiny():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable,
         os.path.join(BENCH, "reference_check_qwen3_next.py"), "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert "reference_check_qwen3_next: PASS" in p.stdout
    assert "the bfloat16 reference must NOT be judged correct" in p.stdout
    assert "the bfloat16 reference's gradient of l0.gdn.A_log" in p.stdout
    assert "held assignments and padded rows per layer" in p.stdout
