"""The fused backward flash kernel is one custom call named
`%flash_dq_flash_dkv.N`. The two metrics that read the backward kernel by
name (`flash_bwd_ms.train`, `flash_bwd_calls.train`) find it once, the sums
over the flash kernels count it once, and the forward kernel's metrics do not
read it. `flash_dq_ms.train` and `flash_dkv_ms.train`, which each read the
one call by the substring of their own name, were retired in PR 45: the
benchmark lists neither and holds no file of theirs."""

import json
import os

import pytest

import trace_reduce as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FUSED = ("%flash_dq_flash_dkv.3 = (bf16[96,2048,64]{2,1,0}, "
         "bf16[96,2048,64]{2,1,0}, bf16[96,2048,64]{2,1,0}) "
         "custom-call(%bitcast.9, %bitcast.10), "
         "custom_call_target=\"tpu_custom_call\"")
# under `jax.vjp` (the grad op's fallback, `jax.grad` of flash_attention)
FUSED_IN_VJP = FUSED.replace("%flash_dq_flash_dkv.3",
                             "%jvp_flash_dq_flash_dkv_.7")
FWD = ("%flash_fwd.4 = (bf16[96,2048,64]{2,1,0}, f32[96,1,2048]{2,1,0}) "
       "custom-call(%bitcast.6), custom_call_target=\"tpu_custom_call\"")
# an op that only consumes the kernel's result is not the kernel
USER = ("%get-tuple-element.5 = bf16[96,2048,64]{2,1,0} "
        "get-tuple-element(%flash_dq_flash_dkv.3), index=0")
BY_NAME = {FUSED: 700, FUSED_IN_VJP: 300, FWD: 400, USER: 50}


def pattern(metric):
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] in ("trace_ops", "trace_calls"), metric
    return spec["args"]["pattern"]


@pytest.mark.parametrize("metric", ["flash_bwd_ms.train",
                                    "flash_bwd_calls.train"])
def test_backward_metrics_find_the_fused_call_once(metric):
    assert tr.sum_matching(BY_NAME, pattern(metric)) == (
        1000, sorted([FUSED, FUSED_IN_VJP]))
    assert tr.sum_matching({FUSED: 700}, pattern(metric)) == (700, [FUSED])


@pytest.mark.parametrize("metric", ["custom_call_ms.train",
                                    "attention_kernels_ms.train"])
def test_sums_over_the_kernels_count_the_fused_call_once(metric):
    assert tr.sum_matching(BY_NAME, pattern(metric)) == (
        1400, sorted([FUSED, FUSED_IN_VJP, FWD]))


@pytest.mark.parametrize("metric", ["flash_fwd_ms.train",
                                    "flash_fwd_calls.train"])
def test_forward_metrics_do_not_read_the_fused_call(metric):
    assert tr.sum_matching(BY_NAME, pattern(metric)) == (400, [FWD])


def test_the_split_pair_is_not_the_fused_kernel():
    """Where a block falls back to the split kernels the engagement counter
    does not count them."""
    split = {FUSED.replace("%flash_dq_flash_dkv.3", "%flash_dq.3"): 1,
             FUSED.replace("%flash_dq_flash_dkv.3", "%flash_dkv.3"): 2}
    for metric in ("flash_bwd_ms.train", "flash_bwd_calls.train"):
        assert tr.sum_matching(split, pattern(metric)) == (0, [])


@pytest.mark.parametrize("metric", ["flash_dq_ms.train",
                                    "flash_dkv_ms.train"])
def test_the_pair_that_read_one_call_twice_is_retired(metric):
    """Neither a file under `metrics/` nor an entry of BENCHMARK.json: a
    listed metric without a file exits before any run, and a file that no
    entry lists is read by nothing."""
    assert not os.path.exists(os.path.join(BENCH, "metrics",
                                           metric + ".json"))
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        listed = [m["name"] for m in json.load(f)["per_layer"]]
    assert metric not in listed
    assert {"flash_bwd_ms.train", "flash_bwd_calls.train"} <= set(listed)
