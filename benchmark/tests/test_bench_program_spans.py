"""The readers of the program's own spans, kernel names and compile stages,
each on a hand-made event list; every expected number is worked out in the
comments. With a program that has none of them (the parent of the PR that
added them) every reader gives nothing and raises nothing."""

import collections
import json
import os

import pytest

import trace_reduce as tr
from readers import (compile_stages, idle_by_span, program_spans, trace_calls,
                     trace_ops)
from trace_reduce import Event

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPS, D0, HOST = tr.OPS_LINE, "/device:TPU:0", "/host:CPU"
FWD = ("%flash_fwd.4 = (bf16[768,256,64]{2,1,0}, f32[768,1,256]{2,1,0}) "
       "custom-call(%bitcast.6, %bitcast.442), "
       "custom_call_target=\"tpu_custom_call\"")
FWD_IN_GRAD = FWD.replace("%flash_fwd.4", "%jvp_flash_fwd_.3")
DQ = ("%jvp_flash_dq_.3 = bf16[768,256,64]{2,1,0} custom-call(%bitcast.9), "
      "custom_call_target=\"tpu_custom_call\"")
DKV = DQ.replace("%jvp_flash_dq_.3", "%jvp_flash_dkv_.3")
# an op that only consumes a kernel's result is not the kernel
USER = ("%get-tuple-element.5 = bf16[768,256,64]{2,1,0} "
        "get-tuple-element(%flash_fwd.4), index=0")

EVENTS = [
    # two steps on the host; step 1: run [100,200) = feed [105,115) +
    # state_gather [120,130) + jit_call [130,170) + write_back [170,195)
    Event(HOST, "python3", "bench:dispatch inside exe.run", 95, 110),
    Event(HOST, "python3", "paddle_tpu:run", 100, 100),
    Event(HOST, "python3", "paddle_tpu:feed_convert", 105, 10),
    Event(HOST, "python3", "paddle_tpu:state_gather", 120, 10),
    Event(HOST, "python3", "paddle_tpu:jit_call", 130, 40),
    Event(HOST, "python3", "paddle_tpu:write_back", 170, 25),
    Event(HOST, "python3", "bench:wait in block_until_ready", 205, 195),
    # step 2: run [400,520) = feed [405,425) + state_gather [430,440) +
    # jit_call [440,500) + write_back [500,515)
    Event(HOST, "python3", "paddle_tpu:run", 400, 120),
    Event(HOST, "python3", "paddle_tpu:feed_convert", 405, 20),
    Event(HOST, "python3", "paddle_tpu:state_gather", 430, 10),
    Event(HOST, "python3", "paddle_tpu:jit_call", 440, 60),
    Event(HOST, "python3", "paddle_tpu:write_back", 500, 15),
    Event(HOST, "python3", "PjitFunction(step)", 441, 20),
    # the device: busy [0,150), idle [150,180), busy [180,450), idle
    # [450,480), busy [480,600)
    Event(D0, OPS, FWD, 0, 150),
    Event(D0, OPS, FWD_IN_GRAD, 180, 100),
    Event(D0, OPS, DQ, 280, 70),
    Event(D0, OPS, DKV, 350, 100),
    Event(D0, OPS, USER, 480, 20),
    Event(D0, OPS, FWD, 500, 100),
]


def ctx_of(events, steps=2):
    summary = tr.device_summary(events)
    trace = {"summary": summary, "device": tr.busiest(summary),
             "steps": steps, "spans": tr.host_spans(events, "bench:")}
    return {"obs": {"profile": {"dir": "<hand-made>"}},
            "trace": lambda: trace}


@pytest.fixture
def hand_made(monkeypatch):
    def install(events):
        monkeypatch.setattr(
            program_spans, "load",
            lambda trace_dir: tr.host_spans(events, program_spans.PREFIX))
        return ctx_of(events)
    return install


def metric(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    return spec["reader"], spec.get("args", {})


def test_program_spans_median_of_per_step_sums(hand_made):
    ctx = hand_made(EVENTS)
    spans = program_spans.window_spans(ctx)
    assert program_spans.per_run_ns(spans, {"paddle_tpu:run"}) == [100, 120]
    assert program_spans.per_run_ns(
        spans, {"paddle_tpu:state_gather", "paddle_tpu:write_back"}) == [
            35, 25]
    # through the metric files: medians of two steps, ns -> us
    for name, expect_ns in (("exe_run_us.train", 110),
                            ("exe_feed_us.train", 15),
                            ("exe_state_us.train", 30),
                            ("exe_jit_call_us.train", 50)):
        reader, args = metric(name)
        assert reader == "program_spans"
        assert program_spans.read(ctx, **args) == pytest.approx(
            expect_ns / 1e3)


def test_idle_inside_run_and_gaps_named_by_phase(hand_made, capsys):
    ctx = hand_made(EVENTS)
    # idle 60 ns: gap [150,180) lies in run [100,200) whole, gap [450,480)
    # in run [400,520) whole -> 100 %
    assert idle_by_span.read(ctx) == pytest.approx(100.0)
    out = capsys.readouterr().out
    # [150,180): jit_call [130,170) covers 20, write_back [170,195) 10;
    # [450,480): jit_call [440,500) covers all 30
    assert "paddle_tpu:jit_call 0.0 us, paddle_tpu:jit_call 0.0 us" in out
    # the second step is dispatched later, the caller waits until then:
    # half of the idle is the caller's
    moved = [e._replace(dur_ns=1195) if e.name.startswith("bench:wait")
             else e if e.start_ns < 400 or e.plane != HOST
             else e._replace(start_ns=e.start_ns + 1000) for e in EVENTS]
    ctx = hand_made(moved)
    assert idle_by_span.read(ctx) == pytest.approx(50.0)
    spans = program_spans.window_spans(ctx)
    bench = ctx["trace"]()["spans"]
    assert idle_by_span.name_gaps([(150, 180), (450, 480)], spans, bench) == [
        ("paddle_tpu:jit_call", 30), ("bench:wait in block_until_ready", 30)]
    # a gap that a phase only touches belongs to whoever covers most of it:
    # [160,260) has 10 in jit_call, 25 in write_back, 55 in the wait
    assert idle_by_span.name_gaps([(160, 260)], spans, bench) == [
        ("bench:wait in block_until_ready", 100)]


def test_flash_kernel_metrics_by_pallas_name(hand_made):
    ctx = hand_made(EVENTS)
    # flash_fwd: FWD 150 + 100, FWD_IN_GRAD 100 = 350 ns over 2 steps;
    # the get-tuple-element that reads %flash_fwd.4 is not counted
    reader, args = metric("flash_fwd_ms.train")
    assert reader == "trace_ops"
    assert trace_ops.read(ctx, **args) == pytest.approx(350 / 1e6 / 2)
    # the backward here is the split pair (DQ 70, DKV 100), which the fused
    # kernel's two metrics do not read: nothing, not 0
    for name, module in (("flash_bwd_ms.train", trace_ops),
                         ("flash_bwd_calls.train", trace_calls)):
        assert module.read(ctx, **metric(name)[1]) is None
    # two call sites: the forward op's and the one inside the grad op
    reader, args = metric("flash_fwd_calls.train")
    assert reader == "trace_calls"
    assert trace_calls.read(ctx, **args) == 2.0
    # the three kernels together are custom_call_ms.train
    _, args = metric("custom_call_ms.train")
    assert trace_ops.read(ctx, **args) == pytest.approx(520 / 1e6 / 2)


def test_compile_stages_summed_over_the_programs_events():
    E = collections.namedtuple("E", "program_uid cause stages_s")
    events = [E(1, "first_call", {"trace": 0.5, "backend": 3.0}),
              E(2, "first_call", {"trace": 2.0, "lower": 1.0,
                                  "backend": 9.0}),
              E(2, "feed_shape", {"lower": 1.5, "backend": 8.0})]
    assert compile_stages.program_stages(events, 2) == {
        "trace": 2.0, "lower": 2.5, "backend": 17.0}
    assert compile_stages.program_stages(events, 3) is None


def test_a_program_without_spans_names_or_stages_gives_nothing(hand_made):
    """What the parent commit's trace looks like: the benchmark's spans
    only, Mosaic calls named after the jitted function."""
    old = [e._replace(name=e.name.replace("%flash_fwd", "%step")
                      .replace("%jvp_flash_fwd_", "%jvp__")
                      .replace("%jvp_flash_dq_", "%transpose_jvp___")
                      .replace("%jvp_flash_dkv_", "%transpose_jvp___"))
           for e in EVENTS if not e.name.startswith("paddle_tpu:")]
    ctx = hand_made(old)
    for name in ("exe_run_us.train", "exe_state_us.train",
                 "flash_fwd_ms.train", "flash_bwd_ms.train",
                 "flash_bwd_calls.train", "flash_fwd_calls.train",
                 "idle_inside_run_pct.train"):
        reader, args = metric(name)
        module = {"program_spans": program_spans, "trace_ops": trace_ops,
                  "trace_calls": trace_calls,
                  "idle_by_span": idle_by_span}[reader]
        assert module.read(ctx, **args) is None, name
    # no profile at all (an untraced run), and an event without stages_s
    assert program_spans.read({"obs": {"profile": None}},
                              ["paddle_tpu:run"]) is None
    E = collections.namedtuple("E", "program_uid cause")
    assert compile_stages.program_stages([E(2, "first_call")], 2) is None
