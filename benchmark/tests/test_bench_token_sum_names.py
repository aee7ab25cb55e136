"""A share's token-side sums are custom calls named after their
`pallas_call` (`%moe_token_sum.N`). The two metric files that read them by
name find `moe_combine`'s and `moe_dispatch_grad`'s call at the four share
cells' shapes, and nothing else: not a copy or a fusion that reads a call's
result, not the grouped product beside it, and nothing in the step of the
parent, whose token-side movements are `lax.while_loop`s. And none of the
patterns the four cells already had takes the new calls."""

import json
import os

import pytest

import trace_reduce as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = {"moe_token_sum_kernel_calls.train": ("trace_calls", "count", "higher"),
       "moe_token_sum_kernel_ms.train": ("trace_ops", "ms", "lower")}
KERNELS = ["mellum2_combine", "mellum2_dispatch_grad", "qwen3_next_combine",
           "kanana2_dispatch_grad", "trinity_combine", "in_vjp"]
OTHERS = ["copy", "reader", "gmm", "parent_while", "parent_cast"]
CELLS = ["qwen3_next_80b_a3b.bs1", "kanana_2_30b_a3b.bs1",
         "mellum2_12b_a2_5b.s8192", "trinity_mini_26b_a3b.s4096"]


def names(stem="token_sum"):
    with open(os.path.join(BENCH, "tests", stem + "_trace_names.json")) as f:
        return {k: v for k, v in json.load(f).items()
                if not k.startswith("_")}


def spec(metric):
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        return json.load(f)


def benchmark():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_new_metrics_find_both_callers_and_nothing_else(metric):
    s = spec(metric)
    assert s["reader"] == NEW[metric][0] and set(s["args"]) == {"pattern"}
    assert os.path.isfile(os.path.join(BENCH, "readers", s["reader"] + ".py"))
    texts = names()
    assert sorted(texts) == sorted(KERNELS + OTHERS)
    by_name = {text: 100 * (i + 1) for i, text in enumerate(texts.values())}
    assert tr.sum_matching(by_name, s["args"]["pattern"]) == (
        sum(by_name[texts[k]] for k in KERNELS),
        sorted(texts[k] for k in KERNELS))


@pytest.mark.parametrize("key", OTHERS)
@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_new_metrics_leave_out(metric, key):
    pattern = spec(metric)["args"]["pattern"]
    assert tr.sum_matching({names()[key]: 1}, pattern) == (0, [])


@pytest.mark.parametrize("stem", ["mellum2", "kanana2", "qwen3_next",
                                  "trinity"])
@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_parents_step_holds_nothing_for_them(metric, stem):
    """The instruction texts of the four cells' steps as the chip's traces
    carried them before PR 50: nothing to read, so the readers return
    nothing and the parent's line leaves both metrics out."""
    pattern = spec(metric)["args"]["pattern"]
    assert tr.sum_matching({t: 1 for t in names(stem).values()},
                           pattern) == (0, [])


def test_both_metrics_read_one_pattern():
    a, b = (spec(m)["args"]["pattern"] for m in sorted(NEW))
    assert a == b


def _patterns_of(cell):
    """(metric, pattern) of every per-layer metric the cell reports that
    goes by an instruction's text, the two new ones left out."""
    for m in benchmark()["per_layer"]:
        if m["name"] in NEW or cell not in m.get("workloads", [cell]):
            continue
        args = spec(m["name"]).get("args", {})
        pattern = args.get("pattern") or args.get("ops")
        if pattern:
            yield m["name"], pattern


@pytest.mark.parametrize("cell", CELLS)
def test_the_cells_other_patterns_do_not_take_the_calls(cell):
    """The calls' results (`bf16[4096,2048]`, `bf16[8192,2304]`) and names
    fall in none of the shape or name patterns a share cell already had:
    no grouped product's, no attention kernel's, no rotary or norm
    kernel's time grows by the sums'."""
    texts = names()
    found = list(_patterns_of(cell))
    assert len(found) >= 5
    for metric, pattern in found:
        assert tr.sum_matching({texts[k]: 1 for k in KERNELS},
                               pattern) == (0, []), metric


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_entries_list_the_four_share_cells(metric):
    (entry,) = [m for m in benchmark()["per_layer"] if m["name"] == metric]
    _, unit, better = NEW[metric]
    assert entry == {"name": metric, "unit": unit, "better": better,
                     "source": "device_trace", "layer": "expert layer",
                     "moves": "train_examples_per_s", "workloads": CELLS}
    # the last two of the list: nothing that was there has moved
    assert [m["name"] for m in benchmark()["per_layer"][-2:]] == sorted(NEW)
