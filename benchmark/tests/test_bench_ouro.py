"""The Ouro configuration's own pieces of the yardstick: its FLOP and
attention counts against numbers worked out by hand, each new metric's
pattern against instruction text at the cell's shapes (as the compiler for a
described v5e names them) on a hand-made event list, the new reader on a
hand-made observatory, the reference kept identical to the tests' copy, the
configuration against the catalog's numbers, `reference_check_ouro.py
--tiny`, and the host-fed cell's data files. (`run.py --tiny` of both new
cells, both ways, is `test_bench_run_tiny.py`'s, which runs every file under
`workloads/`.)"""

import filecmp
import importlib
import json
import os
import subprocess
import sys
import types

import pytest

import trace_reduce as tr
from readers import compile_detail, roofline, trace_calls, trace_ops
from trace_reduce import Event

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def flops(seq_len=4096, **over):
    c = load("configs", "ouro_2_6b.json")
    fn = importlib.import_module("flops." + c["flops"]).flops_per_example
    return fn(**dict(c["build_args"], seq_len=seq_len, **over))


def test_ouro_flops_by_hand():
    # multiply-adds a token, one layer application: q, k, v, o 4 * 2048^2 =
    # 16,777,216; attention, causal half: T * d = 4096 * 2048 = 8,388,608;
    # gate, up, down 3 * 2048 * 5632 = 34,603,008
    application = 16_777_216 + 8_388_608 + 34_603_008
    assert application == 59_768_832                    # "59.8 M"
    head = 2048 * 49152
    assert head == 100_663_296                          # "100.7 M"
    per_token = 16 * application + 4 * head + 3 * 2048  # three gates
    got = flops()
    assert got["forward"] == 2 * 4096 * per_token
    assert got["forward_backward"] == 3 * got["forward"]
    assert got["forward_backward"] / 1e12 == pytest.approx(33.4, abs=0.05)
    assert got["positions_per_example"] == 4096
    assert got["layer_applications"] == 16
    # what depth 4 does to the shares (48 layers in brackets)
    assert got["heads_share"] == pytest.approx(0.296, abs=1e-3)
    assert got["looped_stack_share"] == pytest.approx(0.704, abs=1e-3)
    full = flops(n_layer=48)
    assert full["heads_share"] == pytest.approx(0.034, abs=1e-3)
    assert full["layer_applications"] == 192
    assert got["multiply_adds_per_token_application"]["feed_forward"] == \
        34_603_008


def test_attention_counts_by_hand():
    # an attention block: seven T x T x d_model products, the causal half
    # of each: 7 * 2 * 4096^2 * 2048 / 2 = 240,518,168,576 FLOP; sixteen
    # blocks; eight [4096, 2048] bf16 tensors a block = 134,217,728 bytes
    got = flops()
    block = 7 * 4096 * 4096 * 2048
    assert block == 240_518_168_576
    assert got["attention_flops"] == 16 * block
    assert got["attention_bytes"] == 16 * 8 * 4096 * 2048 * 2
    # compute-bound: 19.53 ms of products against 2.62 ms of traffic a step
    assert 16 * block / 197e12 == pytest.approx(19.53e-3, rel=1e-3)
    assert 16 * 134_217_728 / 819e9 == pytest.approx(2.62e-3, rel=2e-3)
    # the count is the mask's exact half; the kernels compute whole tiles on
    # the diagonal (1024 x 1024 here: 10 of 16 tiles, 62.5%), so the work
    # done is never under the count and the share cannot pass 100%
    sys.path.insert(0, ROOT)
    from paddle_tpu.ops import pallas_attention as pa
    assert pa._blk(4096, True) == (1024, 1024)


OPS = tr.OPS_LINE
D0 = "/device:TPU:0"
TARGET = "custom_call_target=\"tpu_custom_call\""
NAMES = {
    "fwd_a": "%flash_fwd.16 = (bf16[16,4096,128]{2,1,0:T(8,128)(2,1)S(1)}, "
             "f32[16,1,4096]{2,1,0:T(1,128)}) custom-call(%constant.146, "
             "%get-tuple-element.577, %get-tuple-element.576, %fusion.1266), "
             + TARGET,
    "fwd_b": "%flash_fwd.17 = (bf16[16,4096,128]{2,1,0:T(8,128)(2,1)S(1)}, "
             "f32[16,1,4096]{2,1,0:T(1,128)}) custom-call(%constant.146, "
             "%get-tuple-element.575, %get-tuple-element.574, %fusion.1541), "
             + TARGET,
    "bwd": "%flash_dq_flash_dkv.16 = (bf16[16,4096,128]{2,1,0:T(8,128)(2,1)}"
           ", bf16[16,4096,128]{2,1,0:T(8,128)(2,1)}, bf16[16,4096,128]"
           "{2,1,0:T(8,128)(2,1)S(1)}) custom-call(%constant.146, "
           "%custom-call.289, %custom-call.288), " + TARGET,
    "head": "%fusion.2381 = (bf16[4096]{0:T(1024)(128)(2,1)S(1)}, "
            "f32[4096,49152]{1,0:T(8,128)}, bf16[4096,49152]{1,0:T(8,128)"
            "(2,1)}) fusion(%convert_element_type.1099, %copy-done.211), "
            "kind=kOutput, calls=%fused_computation.3170",
    "head_dw": "%fusion.31 = bf16[2048,49152]{1,0:T(8,128)(2,1)} "
               "fusion(%a, %b), kind=kOutput, calls=%fused_computation.40",
    "head_adam": "%divide_subtract_fusion = (f32[2048,49152]{1,0:T(8,128)}, "
                 "f32[2048,49152]{1,0:T(8,128)}, f32[2048,49152]{1,0:T(8,128)"
                 "}) fusion(%p, %m, %v), kind=kLoop, calls=%fused_c.1",
    "scatter": "%fusion.7 = f32[49152,2048]{1,0:T(8,128)} fusion(%z, %ids, "
               "%g), kind=kCustom, calls=%fused_computation.7",
    "head_dx": "%fusion.436 = (f32[2048]{0:T(1024)}, bf16[4096,2048]{0,1:T(8,"
               "128)(2,1)}) fusion(bf16[4096,49152]{1,0:T(8,128)(2,1)} %d, "
               "bf16[2048,49152]{1,0:T(8,128)(2,1)} %w), kind=kOutput, "
               "calls=%fused_computation.4915",
    "mlp": "%fusion.1671 = bf16[2048,5632]{1,0:T(8,128)(2,1)} fusion(bf16["
           "4096,2048]{0,1:T(8,128)(2,1)} %x, bf16[4096,5632]{1,0:T(8,128)(2,"
           "1)} %y), kind=kOutput, calls=%fused_computation.49152",
}
# two steps; per step: two forward calls of 1.0 ms, a backward of 1.5 ms,
# head 4, weight gradient 3, Adam 2, scatter 1, the head's input gradient 5
# (vocabulary-wide operands); a layer's weight gradient whose computation is
# numbered 49152 (7 ms) neither makes nor reads a vocabulary-wide array
MS = {"fwd_a": 1.0, "fwd_b": 1.0, "bwd": 1.5, "head": 4.0, "head_dw": 3.0,
      "head_adam": 2.0, "scatter": 1.0, "head_dx": 5.0, "mlp": 7.0}


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


def test_cell_patterns_on_a_hand_made_trace():
    assert metric("loop_attention_kernels_ms.train") == pytest.approx(3.5)
    assert metric("loop_flash_fwd_calls.train") == 2.0
    # a vocabulary-wide result or operand: head, its weight gradient, its
    # Adam, the embedding's scatter-add, the head's input gradient; not
    # `calls=...49152`
    assert metric("vocab_ops_ms.train") == pytest.approx(15.0)
    # 16 blocks need 19.53 ms of products; this trace shows 3.5 ms a step
    # (three of 32 calls): 558%, reported as it comes out, never clipped
    assert metric("loop_attention_roofline_pct.train") == \
        pytest.approx(100 * (16 * 240_518_168_576 / 197e12) / 3.5e-3)


def test_patterns_hold_the_cells_shapes():
    """`vocab_ops_ms.train` finds its ops by the vocabulary written into the
    pattern: every cell that lists it has to have that vocabulary."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "vocab_ops_ms.train")
    pattern = load("metrics", "vocab_ops_ms.train.json")["args"]["pattern"]
    assert entry["workloads"] == ["ouro_2_6b.bs1"]
    for name in entry["workloads"]:
        cell = load("workloads", name + ".json")
        args = load("configs", cell["config"] + ".json")["build_args"]
        assert str(args["vocab_size"]) in pattern
    new = {"loop_attention_kernels_ms.train", "loop_flash_fwd_calls.train",
           "loop_attention_roofline_pct.train", "vocab_ops_ms.train",
           "grad_fanin_max.train"}
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == ["ouro_2_6b.bs1"], m["name"]
            assert m["moves"] == "train_examples_per_s"


def test_compile_detail_reader_on_a_hand_made_observatory(monkeypatch):
    def event(uid, detail):
        return types.SimpleNamespace(program_uid=uid, detail=detail)
    events = [event(3, {"version": 1, "grad_fanin_max": 0}),     # startup
              event(5, {"version": 2, "parameters": 49,
                        "parameter_uses": 191, "grad_fanin_max": 4}),
              event(5, {"shapes": {}}),         # a later shape miss: no key
              event(9, {"version": 1})]         # a program older than the key
    assert compile_detail.program_detail(events, 5, "grad_fanin_max") == 4
    assert compile_detail.program_detail(events, 5, "parameters") == 49
    assert compile_detail.program_detail(events, 3, "grad_fanin_max") == 0
    assert compile_detail.program_detail(events, 9, "grad_fanin_max") is None
    assert compile_detail.program_detail(events, 7, "grad_fanin_max") is None
    assert compile_detail.program_detail(
        [types.SimpleNamespace(program_uid=5)], 5, "grad_fanin_max") is None

    sys.path.insert(0, ROOT)
    from paddle_tpu import observe
    monkeypatch.setattr(observe.observatory(), "events", lambda: events)
    system = types.SimpleNamespace(main=types.SimpleNamespace(_uid=5))
    assert metric("grad_fanin_max.train", {"system": system}) == 4.0
    system.main._uid = 9
    assert metric("grad_fanin_max.train", {"system": system}) is None


def test_reference_copies_are_identical():
    assert filecmp.cmp(
        os.path.join(BENCH, "references", "ouro_reference.py"),
        os.path.join(ROOT, "tests", "ouro_reference.py"), shallow=False)


def test_config_holds_the_catalog_numbers_and_lists_only_the_depth():
    c = load("configs", "ouro_2_6b.json")
    published = {"hidden_size": 2048, "intermediate_size": 5632,
                 "head_dim": 128, "num_attention_heads": 16,
                 "num_key_value_heads": 16, "vocab_size": 49152,
                 "max_position_embeddings": 65536, "max_window_layers": 48,
                 "rms_norm_eps": 1e-6, "rope_theta": 1000000,
                 "total_ut_steps": 4, "early_exit_threshold": 1,
                 "tie_word_embeddings": False, "hidden_act": "silu"}
    for key, value in published.items():
        assert c[key] == value, key
    assert len(c["layer_types"]) == 48          # the group, copied whole
    assert c["num_hidden_layers"] == 4          # published: 48; in `reduced`
    b = c["build_args"]
    assert (b["d_model"], b["n_head"], b["d_ff"], b["vocab_size"],
            b["n_layer"], b["n_loop"], b["rope_theta"], b["rms_eps"]) == \
        (2048, 16, 5632, 49152, 4, 4, 1e6, 1e-6)
    assert len(c["reduced"]) == 1 and \
        c["reduced"][0].startswith("num_hidden_layers 48 -> 4")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [e for e in json.load(f)["configs"]
                  if e["name"] == "ouro_2_6b"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == c["source"]


def test_traffic_is_olmoes_but_for_the_reference_and_generator():
    old = load("traffic", "steady_b1_s4096.json")
    new = load("traffic", "steady_b1_s4096_ouro.json")
    for key in ("batch", "build_args", "pool_batches", "feed", "in_flight",
                "warmup", "traced"):
        assert new[key] == old[key], key
    assert new["generator"] == "train_loop_reference"
    assert new["reference_check"]["reference"] == "ouro_reference"
    assert len(new["reference_check"]["loss_atol_why"]) > 200


def test_hostfed_traffic_is_steady_b128_with_the_feed_on_the_host():
    old = load("traffic", "steady_b128.json")
    new = load("traffic", "hostfed_b128.json")
    assert new["feed"] == "host" and old["feed"] == "device"
    for key in old:
        if key not in ("feed", "what"):
            assert new[key] == old[key], key
    cell = load("workloads", "resnet50.bs128.hostfed.json")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("resnet50", "hostfed_b128", 1)


def test_reference_check_tiny():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "reference_check_ouro.py"),
         "--tiny"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert "reference_check_ouro: PASS" in p.stdout
    assert "the bfloat16 reference must NOT be judged correct" in p.stdout
    assert "the bfloat16 reference's pass 4 logits" in p.stdout
    assert "the bfloat16 reference's gradient of exit_gate.w" in p.stdout
