"""`readers/trace_scopes.py` on a hand-made compiled text and device track
(the numbers below are worked out by hand in the comments), and the six
metric files that read through it."""

import inspect
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

import sys  # noqa: E402

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from readers import trace_scopes  # noqa: E402

NEW = ["op_attributed_pct.train", "optimizer_op_ms.train",
       "layer_norm_op_ms.train", "moe_layout_op_ms.train",
       "gdn_op_ms.train", "causal_conv_op_ms.train"]

# a step as jax prints it: a fused computation with two ops' instructions,
# a while loop whose body the device shows as events of their own, one
# instruction of XLA's own
TEXT = '''HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %mul.1 = f32[8]{0} multiply(%p0, %p1), metadata={op_name="jit(step)/l0.moe/moe_combine/mul"}
  ROOT %add.1 = f32[8]{0} add(%mul.1, %p1), metadata={op_name="jit(step)/adam/add"}
}

%body.2 (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%t), index=1
  %sort.3 = f32[8]{0} sort(%gte.1), dimensions={0}, to_apply=%cmp, metadata={op_name="jit(step)/l0.moe/moe_dispatch/while/body/sort"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.1, %sort.3)
}

%cond.2 (t.1: (s32[], f32[8])) -> pred[] {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(false)
}

ENTRY %main.9 (a: f32[8], b: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="feeds['a']"}
  %b = f32[8]{0} parameter(1)
  %copy.4 = f32[8]{0} copy(%a)
  %init = (s32[], f32[8]{0}) tuple(%copy.4, %b)
  %while.5 = (s32[], f32[8]{0}) while(%init), condition=%cond.2, body=%body.2, metadata={op_name="jit(step)/l0.moe/moe_dispatch/while"}
  %gte.6 = f32[8]{0} get-tuple-element(%while.5), index=1
  %gdn_fwd.7 = f32[8]{0} custom-call(%gte.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/l1.gdn/gated_delta_rule/gdn_fwd"}
  ROOT %fusion.8 = f32[8]{0} fusion(%gdn_fwd.7, %b), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/adam/add"}, backend_config={"x":{"y":"1"}}
}
'''

F8 = "f32[8]{0:T(256)}"


def op(start, dur, name):
    return (start, start + dur, name)


# two steps of 1000 ns each, as a TPU names its events: operand types in
# the line, no metadata. copy 50; while 400 spanning a sort of 300; the
# kernel 200; the fusion 250; between steps the device idles
STEP = [
    (0, 50, "%copy.4 = f32[8]{0} copy(f32[8]{0} %a)"),
    (50, 400, "%while.5 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %init),"
              " condition=%cond.2, body=%body.2"),
    (100, 300, "%sort.3 = f32[8]{0} sort(f32[8]{0} %gte.1), dimensions={0},"
               " to_apply=%cmp"),
    (450, 200, "%gdn_fwd.7 = f32[8]{0} custom-call(f32[8]{0} %gte.6), "
               "custom_call_target=\"tpu_custom_call\""),
    (650, 250, "%fusion.8 = f32[8]{0} fusion(f32[8]{0} %gdn_fwd.7, "
               "f32[8]{0} %b), kind=kLoop, calls=%fused_computation.1"),
]
OPS = sorted(op(s + t0, d, n) for t0 in (0, 1000) for s, d, n in STEP)
BUSY_NS = 2 * 900


class _Main:
    _uid = 987654321        # + 1 for every test: each has its own events


class _System:
    main = _Main()


@pytest.fixture
def ctx(monkeypatch):
    from paddle_tpu import observe
    trace_scopes._table.cache_clear()
    _Main._uid += 1
    observe.observatory().record(_Main._uid, "first_call", "executor")
    observe.observatory().latest(_Main._uid).offer_text(lambda: TEXT)
    monkeypatch.setattr(trace_scopes, "_ops", lambda trace_dir, device: OPS)
    trace = {"device": 0, "steps": 2,
             "summary": {0: {"busy_ns": BUSY_NS}}}
    yield {"trace": lambda: trace, "system": _System(),
           "obs": {"profile": {"dir": "unused"}}}
    trace_scopes._table.cache_clear()


def test_ms_a_step_by_owner(ctx, capsys):
    # adam owns the fusion: 250 ns a step
    assert trace_scopes.read(ctx, op="^(adam|momentum)$") == \
        pytest.approx(250e-6)
    # moe_dispatch owns the while at its self time (400 - 300) and the sort
    # in its body (300); moe_combine only sits in adam's fusion
    assert trace_scopes.read(
        ctx, op="^moe_(dispatch|combine)(_grad)?$") == pytest.approx(400e-6)
    assert trace_scopes.read(ctx, op="^gated_delta_rule(_grad)?$") == \
        pytest.approx(200e-6)
    assert trace_scopes.read(ctx, op="^moe_", scope="^l0") == \
        pytest.approx(400e-6)
    assert trace_scopes.read(ctx, op="^moe_", scope="^l1") is None
    # an op the program does not hold: nothing, not 0
    assert trace_scopes.read(ctx, op="^layer_norm(_grad)?$") is None
    # the copy is XLA's own: 2 x 850 of 2 x 900 busy ns have a Fluid owner
    assert trace_scopes.read(ctx, share=True) == \
        pytest.approx(100.0 * 850 / 900)
    out = capsys.readouterr().out
    # the table is printed once, with the fusion's other op beside it
    assert out.count("Fluid op type") == 1
    adam = next(ln for ln in out.splitlines() if ln.startswith("adam "))
    combine_shared = 2 * 250 / 1e6
    assert "moe_combine" not in adam
    assert f"{combine_shared:.3f}" in next(
        ln for ln in out.splitlines() if ln.startswith("moe_combine "))
    assert "the rows sum to 0.001 ms a step" in out
    assert "trace_scopes op='^(adam|momentum)$'" in out


def test_nothing_without_a_tpu_plane_and_nothing_is_lowered(ctx):
    asked = []
    from paddle_tpu import observe
    observe.observatory().latest(_Main._uid).offer_text(
        lambda: asked.append(1) or TEXT)
    no_device = {"device": None, "steps": 2, "summary": {}}
    assert trace_scopes.read(dict(ctx, trace=lambda: no_device),
                             share=True) is None
    assert trace_scopes.read(dict(ctx, trace=lambda: None), op="adam") is None
    assert asked == []


def test_nothing_where_the_program_offers_no_text(ctx):
    from paddle_tpu import observe
    observe.observatory().latest(_Main._uid).offer_text(None)
    assert trace_scopes.read(ctx, share=True) is None
    assert trace_scopes.read(ctx, op="^adam$") is None


def test_a_text_that_is_not_the_running_one_attributes_nothing(ctx):
    from paddle_tpu import observe
    other = TEXT.replace("%gte.6)", "%gte.66)").replace(
        "fusion(%gdn_fwd.7, %b)", "fusion(%gdn_fwd.7, %a)")
    observe.observatory().latest(_Main._uid).offer_text(lambda: other)
    assert trace_scopes.read(ctx, op="^gated_delta_rule$") is None
    assert trace_scopes.read(ctx, op="^adam$") is None
    assert trace_scopes.read(ctx, share=True) == \
        pytest.approx(100.0 * 400 / 900)


@pytest.mark.parametrize("name", NEW)
def test_new_metric_files_name_a_reader_and_arguments_that_exist(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "trace_scopes" and spec["what"]
    accepted = set(inspect.signature(trace_scopes.read).parameters) - {"ctx"}
    assert set(spec["args"]) <= accepted and spec["args"]
    if "op" in spec["args"]:
        import re
        re.compile(spec["args"]["op"])
        assert "\\|" not in spec["args"]["op"]      # a plain alternation
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    cells = {w["name"] for w in bench["workloads"]}
    assert entry["source"] == "device_trace"
    assert entry["moves"] == "train_examples_per_s"
    # listed by name, and only cells that the benchmark has
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_each_listed_cell_holds_the_op_the_metric_reads():
    """A listed metric that reads nothing in a listed cell is
    `output_malformed`: each list holds only cells whose program has the
    op (the configuration's `tiny` program holds the same op types)."""
    import importlib
    import re
    import paddle_tpu as fluid
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    types_of = {}
    for m in bench["per_layer"]:
        if m["name"] not in NEW:
            continue
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            rx = json.load(f)["args"].get("op")
        if rx is None:
            continue
        for cell in m["workloads"]:
            config_name = cells[cell]["config"]
            if config_name not in types_of:
                with open(os.path.join(BENCH, "configs",
                                       config_name + ".json")) as f:
                    config = json.load(f)
                args = dict(config["build_args"], **config["tiny"]["build_args"])
                module, _, fn = config["builder"].partition(":")
                main, startup = fluid.Program(), fluid.Program()
                with fluid.program_guard(main, startup), \
                        fluid.unique_name.guard():
                    _, fetches = getattr(importlib.import_module(module),
                                         fn)(**args)
                    opt = config["optimizer"]
                    getattr(fluid.optimizer, opt["type"])(
                        **opt["args"]).minimize(fetches["loss"])
                types_of[config_name] = {
                    op.type for b in main.blocks for op in b.ops}
            assert any(re.search(rx, t) for t in types_of[config_name]), \
                (m["name"], cell, rx)
