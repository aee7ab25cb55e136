"""The gated norm's two Pallas kernels are custom calls named after their
`pallas_call`s (`%gated_norm_fwd.N`, `%gated_norm_bwd.N`).
`gated_norm_kernel_calls.train` finds both by name and nothing else: not a
user of a kernel's result, not the sum that finishes dScale, not the delta
rule's kernels beside them, and nothing in the step of the parent, whose
gated norm is float32 XLA passes. `gated_norm_op_ms.train` reads by op type:
the op and its registered grad, no other norm. And the rule's shape pattern
(`gdn_scan_ms.train`, which goes by an instruction's first result) finds
neither kernel, so the rule's roofline does not count the norm's time."""

import json
import os
import re

import pytest

import trace_reduce as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNELS = ["fwd", "bwd"]
OTHERS = ["user", "reader", "dscale_sum", "gdn_fwd", "gdn_bwd",
          "parent_copy", "parent_sums", "parent_broadcast"]
CELL = ["qwen3_next_80b_a3b.bs1"]


def names(stem="gated_norm"):
    with open(os.path.join(BENCH, "tests", stem + "_trace_names.json")) as f:
        return {k: v for k, v in json.load(f).items()
                if not k.startswith("_")}


def spec(metric):
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        return json.load(f)


def test_the_calls_metric_finds_both_kernels_and_nothing_else():
    s = spec("gated_norm_kernel_calls.train")
    assert s["reader"] == "trace_calls"
    assert os.path.isfile(os.path.join(BENCH, "readers", "trace_calls.py"))
    pattern = s["args"]["pattern"]
    texts = names()
    by_name = {text: 100 * (i + 1) for i, text in enumerate(texts.values())}
    found = KERNELS + ["in_vjp"]
    assert tr.sum_matching(by_name, pattern) == (
        sum(by_name[texts[k]] for k in found),
        sorted(texts[k] for k in found))


@pytest.mark.parametrize("key", OTHERS)
def test_the_calls_metric_leaves_out(key):
    pattern = spec("gated_norm_kernel_calls.train")["args"]["pattern"]
    assert tr.sum_matching({names()[key]: 1}, pattern) == (0, [])


def test_the_parents_step_holds_nothing_for_the_calls_metric():
    """The instruction texts of the cell's step as the chip's traces
    carried them before PR 48: nothing to read, so the reader returns
    nothing and the parent's line leaves the metric out."""
    pattern = spec("gated_norm_kernel_calls.train")["args"]["pattern"]
    texts = names("qwen3_next")
    assert tr.sum_matching({t: 1 for t in texts.values()},
                           pattern) == (0, [])


@pytest.mark.parametrize("op_type,read", [
    ("gated_rms_norm", True), ("gated_rms_norm_grad", True),
    ("rms_norm", False), ("rms_norm_grad", False),
    ("gated_delta_rule", False), ("gated_delta_rule_grad", False),
    ("layer_norm", False), ("gated_rms_norm_grad_grad", False)])
def test_the_op_metric_reads_the_op_and_its_grad_alone(op_type, read):
    s = spec("gated_norm_op_ms.train")
    assert s["reader"] == "trace_scopes" and set(s["args"]) == {"op"}
    assert bool(re.search(s["args"]["op"], op_type)) == read


@pytest.mark.parametrize("metric", [
    "gdn_scan_ms.train", "gdn_scan_roofline_pct.train",
    "gdn_kernel_ms.train", "gdn_kernel_calls.train",
    "causal_conv_kernel_ms.train", "causal_conv_kernel_calls.train",
    "share_dispatch_ms.train", "hybrid_attention_kernels_ms.train",
    "share_expert_matmul_ms.train",
    "share_expert_matmul_roofline_pct.train"])
def test_the_cells_other_patterns_do_not_take_the_kernels(metric):
    """The kernels' first results (`bf16[1,4096,4096]`) and names fall in
    none of the shape or name patterns the cell already had; the rule's
    shape pattern above all, or `gdn_scan_roofline_pct.train` would count
    the norm's time against the rule's work."""
    args = spec(metric)["args"]
    pattern = args.get("pattern") or args["ops"]
    texts = names()
    assert tr.sum_matching({texts[k]: 1 for k in KERNELS + ["in_vjp"]},
                           pattern) == (0, [])


def test_the_rules_own_patterns_still_find_the_rules_kernels():
    texts = names()
    for metric in ("gdn_scan_ms.train", "gdn_kernel_ms.train"):
        pattern = spec(metric)["args"]["pattern"]
        assert tr.sum_matching({texts["gdn_fwd"]: 2, texts["gdn_bwd"]: 3},
                               pattern)[0] == 5


@pytest.mark.parametrize("metric,unit,better", [
    ("gated_norm_kernel_calls.train", "count", "higher"),
    ("gated_norm_op_ms.train", "ms", "lower")])
def test_the_entries_list_the_one_cell_that_runs_the_op(metric, unit, better):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == metric]
    assert entry == {"name": metric, "unit": unit, "better": better,
                     "source": "device_trace", "layer": "linear attention",
                     "moves": "train_examples_per_s", "workloads": CELL}
