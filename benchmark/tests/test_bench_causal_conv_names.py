"""The causal convolution's two Pallas kernels are custom calls named after
their `pallas_call`s (`%causal_conv_fwd.N`, `%causal_conv_bwd.N`). The two
metric files that read them by name find both, once each, and nothing else:
not the fusions XLA still files under the op, not the delta rule's or the
flash kernels' calls, not an op that only consumes a kernel's result."""

import json
import os

import pytest

import trace_reduce as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# as a TPU trace names them (my chip run, PR 39, a described-v5e compile
# first): the forward's one result, the backward's dX and `[K, C]` dW
FWD = ("%causal_conv_fwd.1 = bf16[1,4096,8192]{2,1,0:T(8,128)(2,1)} "
       "custom-call(bf16[1,4096,8192]{2,1,0:T(8,128)(2,1)} %fusion.77, "
       "bf16[1,4096,8192]{2,1,0:T(8,128)(2,1)} %fusion.77, "
       "f32[4,8192]{1,0:T(4,128)} %bitcast.1), "
       "custom_call_target=\"tpu_custom_call\"")
BWD = ("%causal_conv_bwd.3 = (bf16[1,4096,8192]{2,1,0:T(8,128)(2,1)}, "
       "f32[4,8192]{1,0:T(4,128)}) custom-call(%fusion.77, %fusion.77, "
       "%fusion.912, %bitcast.3), custom_call_target=\"tpu_custom_call\"")
# under `jax.vjp` a kernel's name gains a prefix and a trailing underscore
FWD_IN_VJP = FWD.replace("%causal_conv_fwd.1", "%jvp_causal_conv_fwd_.9")
USER = ("%get-tuple-element.5 = f32[4,8192]{1,0:T(4,128)} "
        "get-tuple-element(%causal_conv_bwd.3), index=1")
FUSION = ("%causal_conv1d_fusion.2 = f32[1,4096,8192]{2,1,0} "
          "fusion(%causal_conv_fwd.1), kind=kLoop")
GDN = ("%gdn_fwd.1 = (f32[64,1,32,128,128]{4,3,2,1,0}, "
       "bf16[1,4096,4096]{2,1,0}) custom-call(%reshape.8), "
       "custom_call_target=\"tpu_custom_call\"")
FLASH = ("%flash_fwd.4 = (bf16[16,4096,256]{2,1,0}, f32[16,1,4096]{2,1,0}) "
         "custom-call(%bitcast.6), custom_call_target=\"tpu_custom_call\"")
BY_NAME = {FWD: 273, BWD: 483, USER: 5, FUSION: 900, GDN: 2790, FLASH: 1260}
NEW = {"causal_conv_kernel_calls.train": "trace_calls",
       "causal_conv_kernel_ms.train": "trace_ops"}


def spec(metric):
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_new_metrics_find_both_kernels_and_nothing_else(metric):
    s = spec(metric)
    assert s["reader"] == NEW[metric]
    assert os.path.isfile(os.path.join(BENCH, "readers", s["reader"] + ".py"))
    pattern = s["args"]["pattern"]
    assert tr.sum_matching(BY_NAME, pattern) == (756, sorted([FWD, BWD]))
    assert tr.sum_matching({FWD_IN_VJP: 7}, pattern) == (7, [FWD_IN_VJP])
    assert tr.sum_matching({USER: 1, FUSION: 2, GDN: 3, FLASH: 4},
                           pattern) == (0, [])


def test_both_metrics_read_one_pattern():
    a, b = (spec(m)["args"]["pattern"] for m in sorted(NEW))
    assert a == b


@pytest.mark.parametrize("metric", [
    "gdn_kernel_ms.train", "gdn_kernel_calls.train", "gdn_scan_ms.train",
    "share_dispatch_ms.train", "hybrid_attention_kernels_ms.train",
    "share_expert_matmul_ms.train"])
def test_the_cells_other_patterns_do_not_take_the_convolution(metric):
    """The kernels' first results (`bf16[1,4096,8192]`) fall in none of the
    shape or name patterns the cell already had."""
    pattern = spec(metric)["args"]["pattern"]
    assert tr.sum_matching({FWD: 1, BWD: 2}, pattern) == (0, [])


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_entries_list_the_one_cell_that_runs_the_op(metric):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == metric]
    assert entry["layer"] == "linear attention"
    assert entry["moves"] == "train_examples_per_s"
    assert entry["source"] == "device_trace"
    assert entry["workloads"] == ["qwen3_next_80b_a3b.bs1"]
