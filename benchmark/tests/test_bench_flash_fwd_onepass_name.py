"""The one-pass forward flash kernel is a custom call named
`%flash_fwd_onepass.N`. Its name holds `flash_fwd`, so every older metric
that reads the forward kernel by name (its time, its call sites, the sums
over the flash kernels and over the custom calls, the roofline share of
the looped cell) reads it as it reads `%flash_fwd.N`; the one new metric,
`flash_fwd_onepass_calls.train`, counts it alone and not the streaming
kernel."""

import json
import os

import pytest

import trace_reduce as tr
from readers import trace_calls

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ONEPASS = ("%flash_fwd_onepass.4 = (bf16[768,256,64]{2,1,0}, "
           "f32[768,1,256]{2,1,0}) custom-call(%bitcast.6), "
           "custom_call_target=\"tpu_custom_call\"")
# under `jax.vjp` (the grad op's fallback, `jax.grad` of flash_attention)
ONEPASS_IN_VJP = ONEPASS.replace("%flash_fwd_onepass.4",
                                 "%jvp_flash_fwd_onepass_.9")
STREAM = ("%flash_fwd.16 = (bf16[16,4096,128]{2,1,0}, "
          "f32[16,1,4096]{2,1,0}) custom-call(%bitcast.2), "
          "custom_call_target=\"tpu_custom_call\"")
BWD = ("%flash_dq_flash_dkv.3 = (bf16[768,256,64]{2,1,0}, "
       "bf16[768,256,64]{2,1,0}, bf16[768,256,64]{2,1,0}) "
       "custom-call(%bitcast.9), custom_call_target=\"tpu_custom_call\"")
# an op that only consumes the kernel's result is not the kernel
USER = ("%get-tuple-element.5 = bf16[768,256,64]{2,1,0} "
        "get-tuple-element(%flash_fwd_onepass.4), index=0")
BY_NAME = {ONEPASS: 400, ONEPASS_IN_VJP: 200, STREAM: 100, BWD: 700,
           USER: 50}

FORWARD_BY_NAME = ["flash_fwd_ms.train", "flash_fwd_calls.train",
                   "loop_flash_fwd_calls.train"]
FLASH_TOGETHER = ["attention_kernels_ms.train",
                  "loop_attention_kernels_ms.train",
                  "loop_attention_roofline_pct.train", "custom_call_ms.train"]


def spec(metric):
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        return json.load(f)


def pattern(metric):
    s = spec(metric)
    assert s["reader"] in ("trace_ops", "trace_calls", "roofline"), metric
    return s["args"]["pattern"]


def test_the_new_metric_counts_the_one_pass_kernel_alone():
    s = spec("flash_fwd_onepass_calls.train")
    assert s["reader"] == "trace_calls"
    ns, names = tr.sum_matching(BY_NAME, s["args"]["pattern"])
    assert (ns, names) == (600, sorted([ONEPASS, ONEPASS_IN_VJP]))
    assert tr.sum_matching({STREAM: 100, BWD: 700, USER: 50},
                           s["args"]["pattern"]) == (0, [])


@pytest.mark.parametrize("sites,count", [(18, 18.0), (0, None)])
def test_the_new_metric_through_its_reader(sites, count):
    """18 call sites read 18; a trace of streaming kernels alone (a cell
    whose rows have several K blocks, the parent's program) reads nothing
    and does not raise."""
    by_name = {ONEPASS.replace(".4 =", f".{i} ="): 10 for i in range(sites)}
    by_name.update({STREAM: 100, BWD: 700})
    trace = {"device": 0, "summary": {0: {"by_name": by_name}}}
    ctx = {"trace": lambda: trace}
    got = trace_calls.read(ctx, **spec("flash_fwd_onepass_calls.train")["args"])
    assert got == count
    assert trace_calls.read({"trace": lambda: None}, pattern="x") is None


@pytest.mark.parametrize("metric", FORWARD_BY_NAME)
def test_forward_metrics_read_the_one_pass_kernel(metric):
    assert tr.sum_matching(BY_NAME, pattern(metric)) == (
        700, sorted([ONEPASS, ONEPASS_IN_VJP, STREAM]))
    assert tr.sum_matching({ONEPASS: 400, BWD: 700}, pattern(metric)) == (
        400, [ONEPASS])


@pytest.mark.parametrize("metric", FLASH_TOGETHER)
def test_sums_over_the_kernels_read_the_one_pass_kernel(metric):
    assert tr.sum_matching(BY_NAME, pattern(metric)) == (
        1400, sorted([ONEPASS, ONEPASS_IN_VJP, STREAM, BWD]))


@pytest.mark.parametrize("metric", ["flash_bwd_ms.train",
                                    "flash_bwd_calls.train"])
def test_backward_metrics_do_not_read_it(metric):
    assert tr.sum_matching(BY_NAME, pattern(metric)) == (700, [BWD])


def test_benchmark_json_lists_the_metric_for_the_transformer_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name: later PRs append their own entries after it
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "flash_fwd_onepass_calls.train"]
    assert entry == {
        "name": "flash_fwd_onepass_calls.train", "unit": "count",
        "better": "higher", "source": "device_trace",
        "layer": "flash kernels", "moves": "train_examples_per_s",
        "workloads": ["transformer_base.seq256", "transformer_base.seq2048"]}
