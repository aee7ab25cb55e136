"""The Kanana-2 configuration's own pieces of the yardstick: its FLOP,
attention and share counts against numbers worked out by hand, each new
metric's pattern against instruction text at the cell's shapes (copied from
the chip's trace of the cell, PR 38) on a hand-made event list, the scope
metrics' expressions against the owners the chip's table showed, the
reference kept identical to the tests' copy, the configuration against the
catalog's numbers, and `reference_check_kanana2.py --tiny`. (`run.py --tiny`
of the cell, both ways, is `test_bench_run_tiny.py`'s, which runs every file
under `workloads/`.)"""

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
from readers import compile_detail, roofline, trace_ops
from trace_reduce import Event

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "kanana_2_30b_a3b.bs1"
CONFIG = "kanana_2_30b_a3b"


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


def test_kanana2_flops_by_hand():
    # multiply-adds a token. MLA: W_q 2048 x (32 x 192), W_kva 2048 x (512 +
    # 64), W_kvb 512 x (32 x 256), W_o (32 x 128) x 2048
    mla = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert mla == 26_345_472                            # "26.35 M"
    # attention, the causal half: scores at 192 and context at 128
    attention = 4096 * 32 * (192 + 128) // 2
    assert attention == 20_971_520
    dense = 3 * 2048 * 6144
    # an expert layer: the router at 128, two shared experts of 768, the
    # routed experts at 6 x 16 / 128 = 0.75 of one a token
    router, shared = 2048 * 128, 3 * 2048 * 1536
    routed = 6 * 16 * 3 * 2048 * 768 // 128
    assert routed == 3_538_944
    head = 2048 * 16032
    per_token = 5 * (mla + attention) + dense + 4 * (router + shared
                                                     + routed) + head
    got = flops()
    assert got["forward"] == 2 * 4096 * per_token
    assert got["forward_backward"] == 3 * got["forward"]
    assert got["forward_backward"] / 1e12 == pytest.approx(8.85, abs=0.005)
    assert got["positions_per_example"] == 4096
    assert got["layers"] == {"dense": 1, "moe": 4}
    # MLA's projections and kernels are two thirds of the step
    assert got["mla_layers_share"] == pytest.approx(0.657, abs=1e-3)
    assert got["attention_kernels_share"] == pytest.approx(0.291, abs=1e-3)
    assert got["experts_share"] == pytest.approx(0.147, abs=1e-3)
    assert got["dense_mlp_share"] == pytest.approx(0.105, abs=1e-3)
    assert got["head_share"] == pytest.approx(0.091, abs=1e-3)
    # all 128 experts held: the routed part is six whole experts a token
    whole = flops(experts_held=None)
    assert whole["multiply_adds_per_token"]["routed_experts"] == \
        6 * 3 * 2048 * 768
    # the published depth: one dense layer and 47 expert layers
    assert flops(n_layer=48)["layers"] == {"dense": 1, "moe": 47}


def test_mla_attention_counts_by_hand():
    got = flops()
    # seven T x T products a head: scores, dK, dQ and the scores again at
    # 192, context, dP and dV at 128; the causal half; 32 heads, 5 layers
    per_head = 4 * 192 + 3 * 128
    assert per_head == 1152
    assert got["mla_attention_flops"] == 5 * 2 * 4096 * 4096 * 32 * 1152 // 2
    assert got["mla_attention_flops"] == 3_092_376_453_120
    # q, k, dq, dk [4096, 32 x 192] and v, Out, dOut, dv [4096, 32 x 128]
    # once each in bf16
    values = 4096 * 32 * (4 * 192 + 4 * 128)
    assert got["mla_attention_bytes"] == 5 * values * 2 == 1_677_721_600
    # the products bound it: 15.7 ms against 2.0 ms of traffic a step
    assert got["mla_attention_flops"] / 197e12 == pytest.approx(15.70e-3,
                                                                 rel=1e-3)
    assert got["mla_attention_bytes"] / 819e9 == pytest.approx(2.048e-3,
                                                                rel=1e-3)
    # with V padded to 192 (what this PR does not do) the three value-side
    # products would be a half wider: 7 x 192 against 1152, a sixth more
    assert 7 * 192 / per_head == pytest.approx(1.1667, abs=1e-4)
    # one width for all: OLMoE's and Ouro's count, seven products of d_model
    same = counts_module().mla_attention_counts(4096, 16, 16, 128, 128)
    assert same["flops"] == 16 * 7 * 2 * 4096 * 4096 * 2048 // 2


def test_share_expert_counts_by_hand():
    got = flops()
    rows = 4096 * 6 * 16 // 128
    assert rows == got["share_expert_rows"] == 3072     # 192 an expert
    # nine products a layer (gate, up, down: forward, input gradient, weight
    # gradient), four expert layers, each rows x 2048 x 768 multiply-adds
    assert got["share_expert_flops"] == 36 * 2 * 3072 * 2048 * 768
    assert got["share_expert_flops"] == 347_892_350_976
    one = (3072 * 2048 + 3072 * 768) * 2
    assert got["share_expert_bytes"] == 36 * one == 622_854_144
    # the products bound it: 1.77 ms against 0.76 ms of traffic
    assert got["share_expert_flops"] / 197e12 == pytest.approx(1.766e-3,
                                                                rel=1e-3)
    assert 36 * one / 819e9 == pytest.approx(0.7605e-3, rel=1e-3)
    # in the deployment an expert sees 8 times the rows: 1536
    deployed = counts_module().share_expert_counts(
        8 * 4096, 4, 2048, 128, 16, 6, 768)
    assert deployed["rows"] == 8 * 3072 and deployed["rows"] // 16 == 1536
    # the layout's rows, of which the held groups use about 3072 + padding
    assert 4096 * 6 + 16 * 128 == 26624


OPS = tr.OPS_LINE
D0 = "/device:TPU:0"
# instruction texts of the cell's step as the chip's trace carried them (my
# chip run, PR 38, call A: one of each kind, operand shapes and all; a long
# operand list cut at "...)"): the three flash kernels at 192 over 128, the
# held experts' kernels, and ops that are neither
with open(os.path.join(BENCH, "tests", "kanana2_trace_names.json")) as f:
    NAMES = json.load(f)
MS = {"fwd": 2.0, "dq": 2.75, "dkv": 3.0,                    # 7.75 a layer
      "gmm": 0.75, "tgmm": 0.25,                            # 1.0
      "q_proj": 0.75, "rope": 0.25, "head": 1.5, "adam_head": 1.75,
      "router": 0.25, "sort": 0.125, "copy_done": 0.125, "scatter": 0.5,
      "while": 0.5}


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
    reader = {"trace_ops": trace_ops, "roofline": roofline,
              "compile_detail": compile_detail}[spec["reader"]]
    return reader.read(context or ctx(), **spec["args"])


def test_trace_names_are_the_cells():
    """No 192-wide value: the forward's result and the backward's dV are
    128 wide, dQ and dK 192, as the chip's trace named them."""
    assert NAMES["fwd"].startswith("%flash_fwd.") and \
        "= (bf16[32,4096,128]{" in NAMES["fwd"]
    assert "= bf16[32,4096,192]{" in NAMES["dq"]
    assert re.search(r"= \(bf16\[32,4096,192\]\{[^}]*\}, bf16\[32,4096,128\]",
                     NAMES["dkv"])
    assert "bf16[26624,2048]" in NAMES["gmm"]
    assert "= bf16[16,2048,768]" in NAMES["tgmm"]


@pytest.mark.parametrize("name,found", [
    ("mla_attention_kernels_ms.train", ("fwd", "dq", "dkv")),
    ("mla_moe_expert_matmul_ms.train", ("gmm", "tgmm"))])
def test_cell_pattern_finds_its_ops_and_no_others(name, found):
    pattern = re.compile(load("metrics", name + ".json")["args"]["pattern"])
    hit = {key for key, text in NAMES.items() if pattern.search(text)}
    assert hit == set(found)
    assert metric(name) == pytest.approx(sum(MS[k] for k in found))


def test_roofline_shares_on_a_hand_made_trace():
    # the kernels need 15.70 ms of products a step (2.05 ms of traffic);
    # this trace shows 7.75 ms (one layer of five): 202.6%, which a real
    # trace of all five layers cannot read; the reader does not clip it
    assert metric("mla_attention_roofline_pct.train") == \
        pytest.approx(100 * (3_092_376_453_120 / 197e12) / 7.75e-3)
    # the held experts' products need 1.766 ms (0.76 ms of traffic); this
    # trace shows 1.0 ms (two of 36 calls)
    assert metric("mla_moe_expert_matmul_roofline_pct.train") == \
        pytest.approx(100 * (347_892_350_976 / 197e12) / 1.0e-3)
    for name, keys in (
            ("mla_attention_roofline_pct.train",
             ("mla_attention_flops", "mla_attention_bytes")),
            ("mla_moe_expert_matmul_roofline_pct.train",
             ("share_expert_flops", "share_expert_bytes"))):
        args = load("metrics", name + ".json")["args"]
        assert (args["flops_key"], args["bytes_key"]) == keys
        assert set(keys) <= set(flops())
    # a count without the keys (another configuration's): nothing, no raise
    other = dict(ctx(), flops={"forward": 1})
    assert metric("mla_attention_roofline_pct.train", other) is None


# (name_scope, op type) of instructions' owners, as the chip's table of the
# cell's traced run listed them (my chip run, PR 38, call A)
OWNERS = [("l0.mla", "mul"), ("l0.mla", "mul_grad"), ("l3.mla", "rms_norm"),
          ("l1.mla", "fused_attention"), ("l4.mla", "fused_attention_grad"),
          ("l2.mla", "rotary_embedding"), ("l2.mla", "rotary_embedding_grad"),
          ("l0.mla", "transpose_grad"), ("l1.mla", "slice"),
          ("l1.mla", "expand"), ("l1.mla", "expand_grad"),
          ("l4.mla", "concat_grad"), ("l3.mla", "unsqueeze_grad"),
          ("l0.mlp", "mul"), ("l0.mlp", "swiglu"), ("l0.mlp", "mul_grad"),
          ("l1.moe", "moe_router"), ("l2.moe", "moe_router_grad"),
          ("l1.moe", "moe_dispatch"), ("l3.moe", "moe_dispatch_grad"),
          ("l4.moe", "moe_combine"), ("l2.moe", "moe_combine_grad"),
          ("l1.moe", "grouped_matmul"), ("l1.moe", "grouped_matmul_grad"),
          ("l1.moe", "swiglu"), ("l1.moe", "sum"), ("l1.moe", "reduce_mean"),
          ("l1.moe", "mul"), ("", "adam"), ("", "lookup_table"),
          ("", "rms_norm"), ("", "softmax_with_cross_entropy")]


def owned(name):
    args = load("metrics", name + ".json")["args"]
    assert load("metrics", name + ".json")["reader"] == "trace_scopes"
    op = re.compile(args["op"]) if "op" in args else None
    scope = re.compile(args["scope"]) if "scope" in args else None
    return {(s, o) for s, o in OWNERS
            if (op is None or op.search(o))
            and (scope is None or scope.search(s))}


def test_scope_metrics_find_their_owners_and_no_others():
    mixer = {(s, o) for s, o in OWNERS if s.endswith(".mla")}
    assert owned("mla_op_ms.train") == mixer and len(mixer) == 13
    assembly = owned("mla_assembly_op_ms.train")
    assert assembly == {(s, o) for s, o in mixer if o.split("_grad")[0] in (
        "rotary_embedding", "transpose", "slice", "expand", "concat",
        "unsqueeze")}
    assert len(assembly) == 8
    # the projections, the norm and the kernels are the mixer's, not the
    # assembly's; the dense layer's ops are neither
    assert not any(o.startswith(("mul", "fused_attention", "rms_norm"))
                   for _, o in assembly)
    assert owned("sigmoid_router_op_ms.train") == {
        ("l1.moe", "moe_router"), ("l2.moe", "moe_router_grad")}
    assert owned("mla_moe_layout_op_ms.train") == {
        ("l1.moe", "moe_dispatch"), ("l3.moe", "moe_dispatch_grad"),
        ("l4.moe", "moe_combine"), ("l2.moe", "moe_combine_grad")}
    # the same expression as the accepted metric of the other expert cells
    assert load("metrics", "mla_moe_layout_op_ms.train.json")["args"] == \
        load("metrics", "moe_layout_op_ms.train.json")["args"]


def test_new_entries_are_listed_for_the_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {"mla_attention_kernels_ms.train",
           "mla_attention_roofline_pct.train", "mla_op_ms.train",
           "mla_assembly_op_ms.train", "sigmoid_router_op_ms.train",
           "mla_moe_expert_matmul_ms.train",
           "mla_moe_expert_matmul_roofline_pct.train",
           "mla_moe_layout_op_ms.train", "router_bias_updates.train"}
    listed = {m["name"]: m for m in bench["per_layer"] if m["name"] in new}
    assert set(listed) == new
    for m in listed.values():
        assert m["workloads"] == [CELL] and \
            m["moves"] == "train_examples_per_s", m["name"]
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".json"))
    assert listed["router_bias_updates.train"]["better"] == "higher"
    # found by name: later PRs append entries, later metrics may take the
    # cell in, later configurations bring cells of their own
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == load("workloads", CELL + ".json")
    assert sum(w["config"] == cell["config"] for w in bench["workloads"]) == 1
    assert len(cell["why"]) <= 200


def test_bias_update_reader_on_a_hand_made_observatory(monkeypatch):
    def event(uid, detail):
        return types.SimpleNamespace(program_uid=uid, detail=detail)
    events = [event(3, {"version": 1, "grad_fanin_max": 0}),     # startup
              event(5, {"version": 2, "grad_fanin_max": 1,
                        "layer_kinds": {"latent_attention": 5},
                        "attention_qk_width": 192,
                        "attention_value_width": 128, "dense_ffn_layers": 1,
                        "moe_router_score": "sigmoid",
                        "moe_router_bias_updates": 4,
                        "moe_experts_routed": 128, "moe_experts_held": 16,
                        "moe_row_buffer_rows": 26624,
                        "moe_share_bounded_moves": 16})]
    sys.path.insert(0, ROOT)
    from paddle_tpu import observe
    monkeypatch.setattr(observe.observatory(), "events", lambda: events)
    system = types.SimpleNamespace(main=types.SimpleNamespace(_uid=5))
    assert metric("router_bias_updates.train", {"system": system}) == 4.0
    system.main._uid = 3        # a program older than the key: left out
    assert metric("router_bias_updates.train", {"system": system}) is None


def test_reference_copies_are_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "references", "kanana2_reference.py"),
        os.path.join(ROOT, "tests", "kanana2_reference.py"), shallow=False)


def test_config_holds_the_catalog_numbers_and_lists_its_three_cuts():
    c = load("configs", CONFIG + ".json")
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 2,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-6,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 1000000, "routed_scaling_factor": 2.448,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    for key, value in published.items():
        assert c[key] == value, key
    # the three cuts, each with what was published beside it
    assert (c["num_hidden_layers"], c["num_hidden_layers_published"]) == \
        (5, 48)
    assert (c["n_routed_experts"], c["n_routed_experts_published"]) == \
        (16, 128)
    assert (c["vocab_size"], c["vocab_size_published"]) == (16032, 128256)
    assert c["vocab_size"] * 8 == c["vocab_size_published"]
    b = c["build_args"]
    assert (b["d_model"], b["d_dense"], b["n_head"], b["kv_rank"],
            b["qk_nope_dim"], b["qk_rope_dim"], b["v_head_dim"],
            b["rope_theta"], b["n_expert"], b["top_k"], b["d_expert"],
            b["n_shared"], b["routed_scaling_factor"], b["rms_eps"]) == \
        (2048, 6144, 32, 512, 128, 64, 128, 1e6, 128, 6, 768, 2, 2.448, 1e-6)
    assert b["qk_nope_dim"] + b["qk_rope_dim"] == c["qk_head_dim"]
    assert (b["n_layer"], b["n_dense_layer"], b["experts_held"],
            b["first_expert"], b["vocab_size"], b["bias_update_rate"]) == \
        (5, 1, 16, 0, 16032, 0.001)
    assert b["n_layer"] - b["n_dense_layer"] >= 4        # the floor
    assert c["feed_ranges"] == {"tokens": [0, 16032], "labels": [0, 16032]}
    assert [r.split()[0] for r in c["reduced"]] == \
        ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert "8 chips share each layer" in c["deployment"]
    for key in ("bias update", "bias start", "bias counts", "balance loss",
                "multi-token prediction", "initialisation", "optimizer",
                "labels", "attention"):
        assert len(c["assumed"][key]) > 30, key
    assert "0.001" in c["assumed"]["bias update"]
    assert c["optimizer"] == {"type": "Adam",
                              "args": {"learning_rate": 1e-6}}
    check = c["reference"]["check"]
    assert {"l0.mla.q.w", "l0.mla.kv_a.w", "l0.mla.kv_norm.w",
            "l0.mla.kv_b.w", "l4.mla.o.w", "l0.mlp.gate.w", "l1.router.w",
            "l1.experts.gate.w", "l4.experts.down.w", "l1.shared.up.w",
            "final_norm.w", "head.w", "embed.w"} == set(check["gradients"])
    assert set(check["loss_atol"]) == {"loss", "ce"}
    for why in (c["reference"]["first_loss_atol_why"], check["why"]):
        assert len(why) > 200 and "TO BE READ" not in why
    assert "TO BE READ" not in json.dumps(c)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [e for e in json.load(f)["configs"] if e["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmark/configs/" + CONFIG + ".json"


def test_traffic_is_qwen3_nexts_but_for_the_reference():
    old = load("traffic", "steady_b1_s4096_qwen3_next.json")
    new = load("traffic", "steady_b1_s4096_kanana2.json")
    for key in ("generator", "batch", "build_args", "pool_batches", "feed",
                "in_flight", "warmup", "traced"):
        assert new[key] == old[key], key
    assert new["reference_check"]["reference"] == "kanana2_reference"
    assert new["reference_check"]["reference_args"] == {"q_block": 512}
    assert len(new["reference_check"]["loss_atol_why"]) > 200
    assert "TO BE READ" not in new["reference_check"]["loss_atol_why"]
    cell = load("workloads", CELL + ".json")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "steady_b1_s4096_kanana2", 1)


def test_reference_check_tiny():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "reference_check_kanana2.py"),
         "--tiny", "--steps", "3"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert "reference_check_kanana2: PASS" in p.stdout
    assert "the bfloat16 reference must NOT be judged correct" in p.stdout
    assert "the bfloat16 reference's gradient of l0.mla.kv_norm.w" in p.stdout
    assert "l2.router.bias after the step is next_bias" in p.stdout
    assert "largest |b| per layer [0.003, 0.003]" in p.stdout
