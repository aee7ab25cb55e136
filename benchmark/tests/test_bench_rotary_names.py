"""The rotary op's Pallas kernel is a custom call named after its
`pallas_call` (`%rotary_fwd.N`, `%rotary_bwd.N`). The two metric files that
read it by name find both ways, at Mellum2's and Kanana-2's shapes, and
nothing else: not a user of a kernel's result, not the fusion that makes the
tables, not the flash kernels, and nothing in the step of the parent, whose
rotary is float32 half-head fusions."""

import json
import os

import pytest

import trace_reduce as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = {"rotary_kernel_calls.train": "trace_calls",
       "rotary_kernel_ms.train": "trace_ops"}
KERNELS = ["q_fwd", "k_bwd", "mla_q_fwd", "mla_k_bwd"]
CELLS = ["mellum2_12b_a2_5b.s8192", "kanana_2_30b_a3b.bs1", "ouro_2_6b.bs1",
         "olmoe_1b_7b.bs1"]


def names(stem):
    with open(os.path.join(BENCH, "tests", stem + "_trace_names.json")) as f:
        return {k: v for k, v in json.load(f).items()
                if not k.startswith("_")}


def spec(metric):
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_new_metrics_find_the_kernel_both_ways_and_nothing_else(metric):
    s = spec(metric)
    assert s["reader"] == NEW[metric]
    assert os.path.isfile(os.path.join(BENCH, "readers", s["reader"] + ".py"))
    pattern = s["args"]["pattern"]
    texts = names("rotary")
    by_name = {text: 100 * (i + 1) for i, text in enumerate(texts.values())}
    ns, found = tr.sum_matching(by_name, pattern)
    assert found == sorted(texts[k] for k in KERNELS + ["in_vjp"])
    assert ns == sum(by_name[texts[k]] for k in KERNELS + ["in_vjp"])
    for key in ("user", "reader", "tables"):
        assert tr.sum_matching({texts[key]: 1}, pattern) == (0, []), key


@pytest.mark.parametrize("stem", ["mellum2", "kanana2", "qwen3_next"])
@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_parents_step_holds_nothing_for_them(metric, stem):
    """The instruction texts of the cells' steps as the chip's traces
    carried them before PR 47 (Mellum2's `%slice_negate_fusion` among
    them): nothing to read, so the reader returns nothing and the parent's
    line leaves the metric out."""
    pattern = spec(metric)["args"]["pattern"]
    texts = names(stem)
    assert tr.sum_matching({t: 1 for t in texts.values()},
                           pattern) == (0, [])


def test_both_metrics_read_one_pattern():
    a, b = (spec(m)["args"]["pattern"] for m in sorted(NEW))
    assert a == b


@pytest.mark.parametrize("metric", [
    "window_attention_kernels_ms.train", "window_attention_calls.train",
    "full_attention_kernels_ms.train", "swa_moe_expert_matmul_ms.train",
    "mla_attention_kernels_ms.train", "mla_moe_expert_matmul_ms.train",
    "loop_attention_kernels_ms.train", "loop_flash_fwd_calls.train",
    "attention_kernels_ms.train", "moe_expert_matmul_ms.train",
    "moe_dispatch_ms.train", "vocab_ops_ms.train"])
def test_the_cells_other_patterns_do_not_take_the_kernel(metric):
    """The kernels' results (`bf16[32,8192,128]`, `bf16[32,4096,64]`) and
    names fall in none of the name or shape patterns the four cells had."""
    pattern = spec(metric)["args"]["pattern"]
    texts = names("rotary")
    assert tr.sum_matching({texts[k]: 1 for k in KERNELS},
                           pattern) == (0, [])


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_entries_list_the_four_cells_that_take_the_kernel(metric):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == metric]
    assert entry["layer"] == "decoder block"
    assert entry["moves"] == "train_examples_per_s"
    assert entry["source"] == "device_trace"
    assert entry["workloads"] == CELLS
