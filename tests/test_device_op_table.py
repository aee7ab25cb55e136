"""Device time by Fluid op (paddle_tpu/profiler.py): the `op_name` parser,
the compiled text a compile event offers, the op map read from it, the join
with a device track (on hand-made events: the CPU has no TPU plane) and the
profiler's table."""

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu import profiler as prof
from paddle_tpu.profiler import Instr


# -- the op_name parser ------------------------------------------------------

@pytest.mark.parametrize("op_name, expected", [
    # plain: the type of the op whose rule emitted the instruction
    ("jit(step)/adam/mul", ("", "adam")),
    # behind the prefixes of fluid.name_scope
    ("jit(step)/ut_step3/mul/dot_general", ("ut_step3", "mul")),
    ("jit(step)/l0.gdn/causal_conv1d/slice", ("l0.gdn", "causal_conv1d")),
    # a grad op, registered or generic
    ("jit(step)/l0.moe/rms_norm_grad/transpose(jvp())/mul",
     ("l0.moe", "rms_norm_grad")),
    ("jit(step)/ut_step3/mul_grad/transpose(jvp())/dot_general",
     ("ut_step3", "mul_grad")),
    # a wrapper's parentheses may hold the whole prefix/op path
    ("transpose(jvp(l0.gdn/rms_norm))/mul", ("l0.gdn", "rms_norm")),
    ("jit(step)/jvp(jit(silu))/l1.moe/swiglu/mul", ("l1.moe", "swiglu")),
    # jax's own loops and branches inside a rule
    ("jit(step)/l0.moe/grouped_matmul_grad/jit(tgmm)/while/body/cond/"
     "branch_1_fun/add", ("l0.moe", "grouped_matmul_grad")),
    ("jit(step)/l3.attn/fused_attention/flash_fwd/while/body/closed_call",
     ("l3.attn", "fused_attention")),
    # a jitted entry point inside a rule
    ("jit(step)/l0.moe/grouped_matmul/jit(gmm)", ("l0.moe", "grouped_matmul")),
    ("jit(step)/lookup_table/jit(_take)/select_n", ("", "lookup_table")),
    # a kernel's own name is the last component
    ("jit(step)/l3.attn/fused_attention/flash_fwd_onepass",
     ("l3.attn", "fused_attention")),
    # a Fluid op that runs a sub-block is passed over for the op inside it
    ("jit(step)/while/while/body/mul/dot_general", ("while", "mul")),
    ("jit(step)/while/while/body/add", ("", "while")),
    # no registered type: XLA's own, or jax code outside every rule
    ("reduce_sum", None),
    ("jit(step)/transpose", None),
    ("jit(step)/jit(_threefry_fold_in)/shift_left", None),
    ("feeds['tokens']", None),
])
def test_parse_op_name(op_name, expected):
    assert prof.parse_op_name(op_name) == expected


def test_bare_reduce_sum_is_a_registered_type_and_still_no_owner():
    # the reason the last component never counts: XLA names instructions
    # of its own after primitives that Fluid has ops of the same name for
    from paddle_tpu.core import registry
    assert registry.is_registered("reduce_sum")
    assert prof.parse_op_name("reduce_sum") is None
    assert prof.parse_op_name("jit(step)/reduce_sum/reduce_sum") == (
        "", "reduce_sum")


# -- the line an instruction is joined on ----------------------------------

TEXT_LINE = ('  ROOT %fusion.7 = (f32[8]{0:T(256)}, f32[8]{0}) fusion(%p.1, '
             '%copy-done.2, /*index=2*/%gmm.3), kind=kLoop, '
             'calls=%fused_computation.4, metadata={op_name="jit(step)/'
             'l0.moe/mul/dot_general" source_file="a}b.py" source_line=3}, '
             'backend_config={"flag_configs":[],"window_config":{"x":["8"]}}')
EVENT_NAME = ('%fusion.7 = (f32[8]{0:T(256)}, f32[8]{0}) fusion('
              'f32[8,2]{1,0:T(8,128)(2,1)S(1)} %p.1, (f32[8]{0}, '
              '(s32[]{:S(2)}), u32[]{:S(2)}) %copy-done.2, '
              'bf16[4,8]{1,0} %gmm.3), kind=kLoop, '
              'calls=%fused_computation.4')


def test_a_text_line_and_its_trace_event_have_one_canonical_form():
    want = ("%fusion.7 = (f32[8]{0:T(256)}, f32[8]{0}) fusion(%p.1, "
            "%copy-done.2, %gmm.3), kind=kLoop, calls=%fused_computation.4")
    assert prof.canonical_line(TEXT_LINE) == want
    assert prof.canonical_line(EVENT_NAME) == want
    # no operands, and attributes that stay
    assert prof.canonical_line(
        "%iota.1 = s32[8]{0} iota(), iota_dimension=0, metadata={op_name="
        '"a"}') == "%iota.1 = s32[8]{0} iota(), iota_dimension=0"
    assert prof.canonical_line("ENTRY %main (a: f32[]) -> f32[] {") is None
    assert prof.canonical_line("}") is None


# -- the compiled text of a compile event, and the op map ---------------------

def _program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[16], dtype="float32")
        with fluid.name_scope("block0"):
            h = layers.layer_norm(layers.fc(input=x, size=16))
        loss = layers.mean(layers.fc(input=h, size=4))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss


FEED = {"x": np.ones((8, 16), np.float32)}


@pytest.fixture(scope="module")
def executor_event():
    main, startup, loss = _program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    return exe, scope, main, observe.observatory().latest(main._uid)


@pytest.fixture(scope="module")
def parallel_event():
    from paddle_tpu.parallel.mesh import make_mesh
    main, startup, loss = _program()
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    pe = fluid.ParallelExecutor(
        loss_name=loss.name, main_program=main, scope=scope,
        mesh=make_mesh([4], ["dp"], jax.devices()[:4]))
    pe.run(fetch_list=[loss.name], feed=FEED)
    return pe, observe.observatory().latest(main._uid)


def _event(request, kind):
    return request.getfixturevalue(kind + "_event")[-1]


@pytest.mark.parametrize("kind", ["executor", "parallel"])
def test_compile_event_offers_the_compiled_text_without_a_feed(request, kind):
    event = _event(request, kind)
    text = event.compiled_text()
    assert "ENTRY" in text and "op_name=" in text
    assert event.source == kind
    # the record that travels (flight recorder, /status) leaves it out
    assert not any("text" in k or "op_map" in k for k in event.as_dict())


@pytest.mark.parametrize("kind", ["executor", "parallel"])
def test_every_entry_instruction_maps_to_an_owner_or_to_none(request, kind):
    event = _event(request, kind)
    text = event.compiled_text()
    op_map = prof.op_map(event)
    entry = text[text.index("\nENTRY"):].splitlines()[2:]
    lines = [prof.canonical_line(ln) for ln in entry]
    lines = [ln for ln in lines if ln]
    assert len(lines) > 10 and all(ln in op_map for ln in lines)
    owners = {i.owner for i in op_map.values() if i.owner}
    types = {t for _, t in owners}
    assert {"mul", "adam", "layer_norm"} <= types or \
        {"mul_grad", "adam"} <= types
    # the name_scope prefix stands before the ops appended inside it, on
    # forward and grad ops alike, and before no other
    scoped = {t for s, t in owners if s == "block0"}
    assert scoped and scoped <= {"mul", "elementwise_add", "layer_norm",
                                 "mul_grad", "elementwise_add_grad",
                                 "layer_norm_grad"}
    assert all(s in ("", "block0") for s, _ in owners)
    assert ("", "adam") in owners
    # XLA's own instructions are kept under their short name
    assert any(i.owner is None and i.short for i in op_map.values())


def test_fusions_list_their_members(executor_event):
    op_map = prof.op_map(executor_event[-1])
    fusions = [(line, i) for line, i in op_map.items() if " fusion(" in line]
    assert fusions
    assert any(i.members for _, i in fusions)
    for _, i in fusions:
        # a fusion's owner is among the ops fused into it whenever the
        # instruction its metadata came from still stands in the fusion
        assert all(len(m) == 2 for m in i.members)
    assert all(not i.members for line, i in op_map.items()
               if " fusion(" not in line)


def test_asking_twice_compiles_once(executor_event):
    event = executor_event[-1]
    asks, text_fn = [], event._text_fn
    event.offer_text(lambda: asks.append(1) or text_fn())
    try:
        event._op_map = None
        first = prof.op_map(event)
        assert first and asks == [1]
        # the op map is what is kept, not the text
        assert prof.op_map(event) is first and asks == [1]
        assert event.compiled_text() and asks == [1, 1]
    finally:
        event.offer_text(text_fn)


_COMPILES = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, seconds, **_: _COMPILES.append(name)
    if name.endswith(("backend_compile_duration",
                      "jaxpr_to_mlir_module_duration")) else None)


def test_an_ask_after_a_run_neither_lowers_nor_compiles(executor_event):
    """Lowered under the context a run calls the step in, from the
    signature a run passes, the ask finds what jax cached for the running
    step: what a traced run pays for the text is its printing."""
    exe, scope, main, event = executor_event
    exe.run(main, feed=FEED, fetch_list=event.detail["fetches"], scope=scope)
    del _COMPILES[:]
    assert event.compiled_text()
    assert exe.compiled_text(main, scope=scope)
    assert _COMPILES == []


def test_executor_compiled_text_is_the_public_accessor(executor_event):
    exe, scope, main, event = executor_event
    text = exe.compiled_text(main, scope=scope)
    assert text == event.compiled_text()
    from tools._common import compile_main_step
    assert compile_main_step(exe, scope, main).as_text() == text
    # no program: the default main program, which this executor never ran
    with pytest.raises(RuntimeError, match="prior run"):
        exe.compiled_text(scope=scope)
    with pytest.raises(RuntimeError, match="prior run"):
        exe.compiled_text(main, scope=fluid.Scope())


def test_parallel_compiled_text_keeps_its_contract(parallel_event):
    pe, event = parallel_event
    text = pe.compiled_text(FEED)
    assert "all-reduce" in text and text == event.compiled_text()
    assert "stablehlo" in pe.lowered_text(FEED) or \
        "mhlo" in pe.lowered_text(FEED)
    with pytest.raises(RuntimeError, match="no compiled step matches"):
        pe.compiled_text({"y": FEED["x"]})


def test_the_text_goes_with_its_scope():
    main, startup, loss = _program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed=FEED, fetch_list=[loss], scope=scope)
    event = observe.observatory().latest(main._uid)
    exe.close()
    del scope
    import gc
    gc.collect()
    # the closure holds no arrays: the scope is referenced weakly
    assert event.compiled_text() is None and prof.op_map(event) is None


# -- the join, on a hand-made device track ------------------------------------

def _line(name, opcode="fusion", operands="%a", rest=""):
    return f"%{name} = f32[8]{{0}} {opcode}({operands}){rest}"


ADAM, MUL, LN = ("", "adam"), ("l0", "mul"), ("l0", "layer_norm")
MAP_A = {
    _line("fusion.1"): Instr(ADAM, (ADAM, MUL), "fusion"),
    _line("fusion.2"): Instr(MUL, (MUL,), "fusion"),
    _line("while.3", "while", "%t", ", condition=%c, body=%b"):
        Instr(LN, (), "while"),
    _line("add.4", "add", "%x, %y"): Instr(LN, (), "add"),
    _line("copy.5", "copy"): Instr(None, (), "copy"),
}


def _event_name(name, opcode="fusion", operands="f32[8]{0} %a", rest=""):
    return _line(name, opcode, operands, rest)


# one step: fusion.1 [0,100), while.3 [100,400) spanning add.4 [120,200)
# and add.4 again [220,320), copy.5 [400,450), fusion.2 [450,500), and a
# fusion whose operand differs from the text's [500,530)
OPS_A = [
    (0, 100, _event_name("fusion.1")),
    (100, 400, _event_name("while.3", "while", "(f32[8]{0}, s32[]) %t",
                           ", condition=%c, body=%b")),
    (120, 200, _event_name("add.4", "add", "f32[8]{0} %x, f32[8]{0} %y")),
    (220, 320, _event_name("add.4", "add", "f32[8]{0} %x, f32[8]{0} %y")),
    (400, 450, _event_name("copy.5", "copy")),
    (450, 500, _event_name("fusion.2")),
    (500, 530, _event_name("fusion.2", operands="f32[8]{0} %other")),
]


def test_self_time_under_a_while():
    times = dict((n.split(" = ")[0], ns) for n, ns in prof.self_times(OPS_A)
                 if "add.4" not in n)
    # the while spans 300 ns of which its body's two ops took 80 + 100
    assert times["%while.3"] == 120
    assert times["%fusion.1"] == 100 and times["%copy.5"] == 50
    adds = [ns for n, ns in prof.self_times(OPS_A) if "add.4" in n]
    assert adds == [80, 100]
    # self times tile the busy time: nothing is counted twice
    assert sum(ns for _, ns in prof.self_times(OPS_A)) == 530


def test_join_owned_shared_xla_and_unattributed():
    table = prof.device_table(OPS_A, MAP_A)
    types, scopes, xla, unattributed, shared = table.rows()
    assert {t: s.total for t, s in types.items()} == {
        "adam": 100, "mul": 50, "layer_norm": 120 + 180}
    assert types["layer_norm"].calls == 3
    assert (types["layer_norm"].min, types["layer_norm"].max) == (80, 120)
    assert {s: st.total for s, st in scopes.items()} == {"l0": 350}
    assert {n: st.total for n, st in xla.items()} == {"copy": 50}
    # a line that differs from the text's is not attributed by its name
    assert (unattributed.calls, unattributed.total) == (1, 30)
    # mul sits in adam's fusion without owning it
    assert dict(shared) == {"mul": 100}
    assert table.total_ns == 530
    assert sum(st.total for _, _, st in table.owned("^adam$")) == 100
    assert sum(st.total for _, _, st in table.owned(
        "^(mul|layer_norm)$", scope="^l0$")) == 350
    assert table.owned("^momentum$") == []


def test_two_programs_in_one_capture_stay_apart():
    map_b = {_line("fusion.1"): Instr(("", "sgd"), (), "fusion"),
             _line("fusion.9"): Instr(("", "sgd"), (), "fusion"),
             _line("dot.8", "dot"): Instr(("", "mul"), (), "dot")}
    ops_b = [(1000, 1040, _event_name("fusion.1")),
             (1040, 1100, _event_name("fusion.9")),
             (1100, 1200, _event_name("dot.8", "dot"))]
    stray = [(2000, 2050, _event_name("custom.1", "custom-call"))]
    modules = [(0, 530, "jit_step(1)"), (1000, 1200, "jit_step(2)"),
               (2000, 2050, "jit_other(3)")]
    out = prof.tables_by_module(modules, sorted(OPS_A + ops_b + stray),
                                {7: MAP_A, 8: map_b})
    by_name = {name: (runs, busy, uid, table)
               for name, runs, busy, uid, table in out}
    runs, busy, uid, table = by_name["jit_step(1)"]
    assert (runs, busy, uid) == (1, 530, 7) and table.total_ns == 530
    runs, busy, uid, table = by_name["jit_step(2)"]
    # `%fusion.1 = ...` stands in both texts; program 8's module is not
    # counted into program 7's table, nor the other way round
    assert (runs, busy, uid) == (1, 200, 8)
    assert {t: s.total for t, s in table.rows()[0].items()} == {
        "sgd": 100, "mul": 100}
    assert by_name["jit_other(3)"][2:] == (None, None)


# -- the table ------------------------------------------------------------------

def _ranked(text):
    """The op types of the table's first block, in printed order."""
    rows = text.split("\n")[1:]
    out = []
    for row in rows:
        if row.startswith(("name_scope", "no Fluid op", "attributed")):
            break
        out.append(row.split()[0])
    return out


@pytest.mark.parametrize("key, order", [
    # adam: 1 call of 100; mul: 1 of 50; layer_norm: 3 calls 80, 100, 120
    ("total", ["layer_norm", "adam", "mul"]),
    ("calls", ["layer_norm", "adam", "mul"]),
    ("max", ["layer_norm", "adam", "mul"]),
    ("min", ["mul", "layer_norm", "adam"]),
    ("ave", ["adam", "layer_norm", "mul"]),
    (None, ["layer_norm", "adam", "mul"]),
])
def test_table_rows_are_ordered_by_sorted_key(key, order):
    text = prof.format_table(prof.device_table(OPS_A, MAP_A), key, 530)
    assert _ranked(text) == order
    assert "name_scope" in text and "no Fluid op" in text
    assert "unattributed (no such line in the compiled text) 5.66%" in text
    assert "in fusions(ms)" in text.split("\n")[0]


def test_an_unknown_sorted_key_is_refused_before_anything_stops(tmp_path):
    with pytest.raises(ValueError, match="sorted_key"):
        prof.format_table(prof.DeviceTable(), "bogus")
    with pytest.raises(ValueError, match="sorted_key"):
        with prof.profiler("All", "bogus", str(tmp_path)):
            raise AssertionError("the block must not run")
    prof.start_profiler("All", str(tmp_path))
    try:
        with pytest.raises(ValueError, match="sorted_key"):
            prof.stop_profiler("longest", str(tmp_path))
    finally:
        # the capture is still on: a valid key ends it
        prof.stop_profiler("ave", str(tmp_path))


def test_profiler_on_the_cpu_says_there_is_no_tpu_plane(tmp_path, capsys,
                                                        executor_event):
    exe, scope, main, event = executor_event
    event._op_map = None
    with prof.profiler("All", "total", str(tmp_path)):
        exe.run(main, feed=FEED, fetch_list=[], scope=scope)
    out = capsys.readouterr().out
    assert "no TPU plane" in out
    # and nothing was lowered for a capture there is no device track in
    assert event._op_map is None
    programs, devices = prof.read_capture(prof._newest_xplane(str(tmp_path)))
    assert main._uid in programs and devices == {}
