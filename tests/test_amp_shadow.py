"""AMP's shadows: a step carries the bf16 form of every float32 parameter it
both updates and hands to a Pallas call (`registry.AMP_SHADOW_OPS`: the expert
stacks `grouped_matmul` reads) from one run to the next, and the op that
writes the parameter writes the next one (core/registry.py::master_as,
core/lowering.py::BlockLowerer, core/executor.py::_StateCache).

What is held here, on the CPU and at small sizes: a shadow is bit for bit its
master's cast after every step; a run whose every step starts from a fresh
gather (the shadows cast from the masters, which is what a step without them
computes) gives the same losses bit for bit; a hand other than the
executor's on the scope is seen by the next step; no float32 -> bf16 convert
of a shadowed master is left in the step, forward, generic grad and
registered grad alike; a program that does not qualify keeps the plain path;
nothing saves a shadow; the same under a mesh.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import registry

STEPS = 5
OPTIMIZERS = {
    "adam": lambda: fluid.optimizer.Adam(learning_rate=1e-2),
    "momentum": lambda: fluid.optimizer.Momentum(learning_rate=1e-2,
                                                 momentum=0.9),
}


def _data(width_out):
    x = layers.data(name="x", shape=[-1, 16], dtype="float32",
                    append_batch_size=False)
    y = layers.data(name="y", shape=[-1, width_out], dtype="float32",
                    append_batch_size=False)
    return x, y


def _routed(x):
    h = layers.rms_norm(layers.fc(input=x, size=16, bias_attr=False,
                                  param_attr=fluid.ParamAttr(name="w_in")))
    return h, layers.moe_router(h, 4, 2, norm_topk_prob=True,
                                param_attr=fluid.ParamAttr(name="router.w"))


def _experts(sharding=None):
    """Two-matrix experts under a router: the stacks are cast by
    `grouped_matmul`'s forward rule and by its registered grad. The dense
    `w_in` (a `mul` takes its cast into itself), the router's weight
    (float32 under AMP) and the norm's get no shadow."""
    x, y = _data(16)
    h, routing = _routed(x)
    out = layers.moe_experts(
        h, routing, 4, 24, name="ex", gated=False, activation="relu2",
        param_attr=fluid.ParamAttr(sharding=sharding))
    loss = layers.mean(layers.square_error_cost(
        layers.cast(out, "float32"), y))
    return loss, ["ex.up.w", "ex.down.w"]


def _share():
    """Gated-silu experts, one chip's share (2 of 4 held): the gate's and
    the up projection's stacks are the two `W` of ONE `grouped_matmul`."""
    x, y = _data(16)
    h, routing = _routed(x)
    out = layers.moe_experts(h, routing, 4, 24, name="ex", first_expert=0,
                             experts_held=2)
    loss = layers.mean(layers.square_error_cost(
        layers.cast(out, "float32"), y))
    return loss, ["ex.gate.w", "ex.up.w", "ex.down.w"]


def _dense():
    """Two `fc` layers with biases (`mul` + `elementwise_add`): nothing
    here is read by a Pallas call, so nothing is shadowed."""
    x, y = _data(8)
    h = layers.fc(input=x, size=32, act="relu",
                  param_attr=fluid.ParamAttr(name="w0"))
    p = layers.fc(input=h, size=8, param_attr=fluid.ParamAttr(name="w1"))
    return layers.mean(layers.square_error_cost(p, y)), []


@pytest.fixture(autouse=True)
def small_stacks_shadowed(monkeypatch):
    """The size a stack needs for a shadow (`AMP_SHADOW_MIN_BYTES`: what the
    chip's fast memory cannot hold) is far over a CPU test's: take it away,
    except in the tests of the size itself."""
    monkeypatch.setattr(registry, "AMP_SHADOW_MIN_BYTES", 0)


MODELS = {"experts": _experts, "share": _share}
CASES = [(m, o) for m in MODELS for o in OPTIMIZERS]


def _build(model, optimizer, build=None):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss, shadowed = (build or MODELS[model])()
        if optimizer is not None:
            OPTIMIZERS[optimizer]().minimize(loss)
    main.random_seed = startup.random_seed = 11
    return main, startup, loss, shadowed


def _feeds(main, n=STEPS, seed=0):
    rng = np.random.RandomState(seed)
    block = main.global_block()
    return [{name: rng.randn(16, block.vars[name].shape[1])
             .astype(np.float32) for name in ("x", "y")} for _ in range(n)]


class _Run:
    """A program started in a scope of its own, stepped through the
    executor's memoized handle."""

    def __init__(self, model, optimizer, amp=True, build=None):
        self.main, startup, self.loss, self.shadowed = _build(
            model, optimizer, build)
        self.scope = fluid.Scope()
        self.exe = fluid.Executor(fluid.CPUPlace(), amp=amp)
        self.exe.run(startup, scope=self.scope)

    def step(self, feed):
        return self.exe.run(self.main, feed=feed, fetch_list=[self.loss],
                            scope=self.scope)[0]

    @property
    def handle(self):
        return self.exe._handle_for(self.main, [self.loss], self.scope)

    @property
    def shadows(self):
        return self.handle._state._shadows

    @property
    def detail(self):
        return self.handle._entry.event.detail


def _is_cast_of(shadow, master):
    assert shadow.dtype == jnp.bfloat16 and shadow.shape == master.shape
    want = np.asarray(jnp.asarray(master).astype(jnp.bfloat16)
                      .astype(jnp.float32))
    return np.array_equal(np.asarray(shadow.astype(jnp.float32)), want)


# -- (a) ---------------------------------------------------------------------
@pytest.mark.parametrize("model,optimizer", CASES)
def test_a_shadow_is_its_masters_cast_after_every_step(model, optimizer):
    run = _Run(model, optimizer)
    before = {}
    for feed in _feeds(run.main):
        run.step(feed)
        assert sorted(run.shadows) == sorted(run.shadowed)
        for name, shadow in run.shadows.items():
            master = run.scope.find_var(name)
            assert master.dtype == jnp.float32
            assert _is_cast_of(shadow, master), name
            # and the step moved the master it shadows
            now = np.asarray(master)
            assert name not in before or not np.array_equal(before[name], now)
            before[name] = now
    assert run.detail["amp_shadowed_params"] == len(run.shadowed)
    assert run.detail["amp_shadowed_mb"] == round(sum(
        2 * run.scope.find_var(n).size for n in run.shadowed) / 1e6, 3)
    assert run.detail["amp_plain_master_casts"] == 0


# -- (b) ---------------------------------------------------------------------
@pytest.mark.parametrize("model,optimizer", CASES)
def test_b_losses_are_those_of_a_run_that_regathers_every_step(model,
                                                               optimizer):
    carried, regathered = _Run(model, optimizer), _Run(model, optimizer)
    gathers = []
    entry_make = None
    for feed in _feeds(carried.main):
        a = carried.step(feed)
        # a hand other than the executor's moves the scope's version: the
        # next step gathers again and casts its shadows from the masters
        regathered.scope.set_var("unrelated", np.zeros(1, np.float32))
        if entry_make is None and regathered.handle._entry is not None:
            entry = regathered.handle._entry
            entry_make = entry.make_shadows
            entry.make_shadows = lambda mut: (gathers.append(1),
                                              entry_make(mut))[1]
        b = regathered.step(feed)
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # every step after the one that bound the entry was a fresh gather
    assert len(gathers) == STEPS - 1
    for name in carried.shadowed:
        assert np.array_equal(np.asarray(carried.scope.find_var(name)),
                              np.asarray(regathered.scope.find_var(name)))


@pytest.mark.parametrize("model,optimizer", CASES)
def test_b_the_carried_step_is_the_step_without_shadows(model, optimizer):
    """The same step lowered without its shadows (the four-argument form
    the tools call: every master cast at its point of use, as before the
    shadows) gives the same loss and the same new state, bit for bit."""
    run = _Run(model, optimizer)
    feeds = _feeds(run.main, 2)
    run.step(feeds[0])
    entry = run.handle._entry
    mut, const = entry.gather_state(run.scope)
    fetches, new_state, _, none = entry._step(feeds[1], mut, const,
                                              np.uint32(1))
    assert none == {}
    loss = run.step(feeds[1])
    assert np.array_equal(np.asarray(fetches[0]), np.asarray(loss))
    for name in entry.mut_names:
        assert np.array_equal(np.asarray(new_state[name]),
                              np.asarray(run.scope.find_var(name))), name


# -- (c) ---------------------------------------------------------------------
@pytest.mark.parametrize("model", MODELS)
def test_c_set_var_between_steps_is_seen_by_the_next_step(model):
    run, twin = _Run(model, "adam"), _Run(model, "adam")
    feeds = _feeds(run.main, 3)
    for r in (run, twin):
        r.step(feeds[0])
    name = run.shadowed[-1]
    planted = np.asarray(run.scope.find_var(name)) * 0.5 + 0.25
    run.scope.set_var(name, jnp.asarray(planted))
    loss = run.step(feeds[1])
    assert _is_cast_of(run.shadows[name], run.scope.find_var(name))
    assert not np.array_equal(np.asarray(loss),
                              np.asarray(twin.step(feeds[1])))
    # the twin told the same thing the long way round: a fresh executor on
    # a scope that holds the same values
    fresh = _Run(model, "adam")
    for n in fresh.scope.local_var_names():
        fresh.scope.set_var(n, twin.scope.find_var(n))
    fresh.exe._run_counts[fresh.main._uid] = 2
    assert np.array_equal(np.asarray(twin.step(feeds[2])),
                          np.asarray(fresh.step(feeds[2])))


@pytest.mark.parametrize("model", MODELS)
def test_c_load_persistables_between_steps_is_seen(model, tmp_path):
    run = _Run(model, "adam")
    feeds = _feeds(run.main, 4)
    run.step(feeds[0])
    fluid.io.save_persistables(run.exe, str(tmp_path), run.main,
                               scope=run.scope)
    saved = {n: np.asarray(run.scope.find_var(n)) for n in run.shadowed}
    first = run.step(feeds[1])
    run.step(feeds[2])
    fluid.io.load_persistables(run.exe, str(tmp_path), run.main,
                               scope=run.scope)
    run.exe._run_counts[run.main._uid] = 1
    again = run.step(feeds[1])
    assert np.array_equal(np.asarray(first), np.asarray(again))
    for name, shadow in run.shadows.items():
        assert _is_cast_of(shadow, run.scope.find_var(name))
        assert not np.array_equal(saved[name],
                                  np.asarray(run.scope.find_var(name)))


# -- (d) ---------------------------------------------------------------------
def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        inner = getattr(value, "jaxpr", value)
        if hasattr(inner, "eqns") and len(inner.invars) == len(eqn.invars):
            yield inner


def _converts_of(jaxpr, masters):
    """float32 -> bf16 `convert_element_type` equations of `jaxpr` (and of
    what it calls) whose operand is one of the variables `masters`."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type" \
                and eqn.params["new_dtype"] == jnp.bfloat16 \
                and any(v is m for v in eqn.invars for m in masters):
            found.append(eqn)
        for inner in _sub_jaxprs(eqn):
            found += _converts_of(
                inner, [iv for iv, ov in zip(inner.invars, eqn.invars)
                        if any(ov is m for m in masters)])
    return found


def _traced(run, feed, with_shadows):
    """(the step's jaxpr, its variables for the shadowed masters)."""
    entry = run.handle._entry
    mut, const = entry.gather_state(run.scope)
    args = (feed, mut, const, np.uint32(0))
    if with_shadows:
        args += (entry.make_shadows(mut),)
    jaxpr = entry._step.trace(*args).jaxpr.jaxpr
    leaves = jax.tree_util.tree_flatten_with_path(args)[0]
    at = [i for i, (path, _) in enumerate(leaves)
          if path[0].idx == 1 and path[1].key in entry.shadow_names]
    assert len(at) == len(entry.shadow_names) == len(jaxpr.invars) \
        - len(leaves) + len(at)
    return jaxpr, [jaxpr.invars[i] for i in at]


@pytest.mark.parametrize("model,optimizer", CASES)
def test_d_no_convert_of_a_shadowed_master_is_left_in_the_step(model,
                                                               optimizer):
    run = _Run(model, optimizer)
    feed = _feeds(run.main, 1)[0]
    run.step(feed)
    jaxpr, masters = _traced(run, feed, with_shadows=True)
    assert _converts_of(jaxpr, masters) == []
    assert run.detail["amp_plain_master_casts"] == 0
    # the walk finds them where they are: the step without its shadows
    # casts every one, in the forward op and again in its grad op
    jaxpr, masters = _traced(run, feed, with_shadows=False)
    assert len(_converts_of(jaxpr, masters)) >= 2 * len(masters)
    # and that trace counted nothing on the running step's event
    assert run.detail["amp_plain_master_casts"] == 0


def _handed(model, monkeypatch, generic_grad=False):
    """The shapes of the shadows `master_as` handed out while one step of
    `model` was traced; `generic_grad`: with `grouped_matmul`'s registered
    grad taken away, so that its grad op re-traces the forward rule under
    `jax.vjp` as the generic grad lowering does for every op without one."""
    handed = []
    orig = registry._shadowed

    def spy(master, shadow):
        handed.append(shadow.shape)
        return orig(master, shadow)

    monkeypatch.setattr(registry, "_shadowed", spy)
    if generic_grad:
        monkeypatch.setattr(registry.get_op_def("grouped_matmul"),
                            "grad_lower", None)
    run = _Run(model, "adam")
    run.step(_feeds(run.main, 1)[0])
    assert run.detail["amp_plain_master_casts"] == 0
    return run, sorted(handed)


@pytest.mark.parametrize("generic_grad", [False, True],
                         ids=["registered_grad", "generic_grad"])
def test_d_forward_rule_and_grad_each_read_the_shadow(monkeypatch,
                                                      generic_grad):
    """The forward rule and the grad op (the registered grad, which asks
    `master_as` itself, or the generic one, which re-traces the forward
    rule on `jax.vjp`'s tracers) are each handed every stack's shadow."""
    run, handed = _handed("experts", monkeypatch, generic_grad)
    assert handed == sorted(2 * [(4, 16, 24), (4, 24, 16)])
    assert "grouped_matmul_grad" in {op.type for op
                                     in run.main.global_block().ops}
    # the gradient reaches the float32 master either way: the step moved it
    before = {n: np.asarray(run.scope.find_var(n)) for n in run.shadowed}
    run.step(_feeds(run.main, 1, seed=1)[0])
    for name in run.shadowed:
        assert not np.array_equal(before[name],
                                  np.asarray(run.scope.find_var(name)))


def test_d_both_stacks_of_one_op_are_handed_out(monkeypatch):
    """Under a share `gate` and `up` are positions 0 and 1 of one `W` slot."""
    _, handed = _handed("share", monkeypatch)
    assert handed == sorted(2 * [(2, 16, 24), (2, 16, 24), (2, 24, 16)])


def test_d_generic_and_registered_grad_agree_bit_for_bit(monkeypatch):
    losses = []
    for generic in (False, True):
        with monkeypatch.context() as m:
            if generic:
                m.setattr(registry.get_op_def("grouped_matmul"),
                          "grad_lower", None)
            run = _Run("experts", "adam")
            losses.append([np.asarray(run.step(f))
                           for f in _feeds(run.main, 3)])
    assert np.array_equal(losses[0], losses[1])


# -- (e) ---------------------------------------------------------------------
def test_e_a_dense_program_has_no_shadow():
    """`mul`, `matmul` and `conv2d` take the cast of a parameter into their
    own fusion: their parameters are cast where they are read, as before."""
    run = _Run(None, "adam", build=_dense)
    for feed in _feeds(run.main, 2):
        run.step(feed)
    assert run.handle._entry.shadow_names == [] and run.shadows == {}
    assert not [k for k in run.detail if k.startswith("amp_")]
    assert {"w0", "w1"} <= set(run.handle._entry.mut_names)


@pytest.mark.parametrize("model", MODELS)
def test_e_a_program_without_amp_has_no_shadow(model):
    run = _Run(model, "adam", amp=False)
    run.step(_feeds(run.main, 1)[0])
    assert run.handle._entry.shadow_names == [] and run.shadows == {}
    assert not [k for k in run.detail if k.startswith("amp_")]


@pytest.mark.parametrize("model", MODELS)
def test_e_an_eval_clone_has_no_shadow(model):
    run = _Run(model, "adam")
    feed = _feeds(run.main, 1)[0]
    run.step(feed)
    evaluate = run.main.clone(for_test=True)
    run.exe.run(evaluate, feed=feed, fetch_list=[run.loss], scope=run.scope)
    handle = run.exe._handle_for(evaluate, [run.loss], run.scope)
    assert handle._entry.mut_names == []
    assert handle._entry.shadow_names == [] and handle._state._shadows == {}
    assert not [k for k in handle._entry.event.detail
                if k.startswith("amp_")]
    # its write-back moved the scope: the training step gathers again
    run.step(feed)
    for name, shadow in run.shadows.items():
        assert _is_cast_of(shadow, run.scope.find_var(name))


def test_e_a_stack_under_the_size_has_no_shadow(monkeypatch):
    """The Program shows the size: a stack whose bf16 form is under
    `AMP_SHADOW_MIN_BYTES` keeps the plain path, one at it gets a shadow."""
    up = 2 * 4 * 16 * 24            # bytes of `ex.up.w`'s bf16 form
    monkeypatch.setattr(registry, "AMP_SHADOW_MIN_BYTES", up + 1)
    run = _Run("experts", "adam")
    run.step(_feeds(run.main, 1)[0])
    assert run.handle._entry.shadow_names == [] and run.shadows == {}
    assert not [k for k in run.detail if k.startswith("amp_")]
    monkeypatch.setattr(registry, "AMP_SHADOW_MIN_BYTES", up)
    run = _Run("experts", "adam")
    run.step(_feeds(run.main, 1)[0])
    assert run.handle._entry.shadow_names == ["ex.up.w", "ex.down.w"]


def test_e_the_size_is_what_fast_memory_cannot_hold():
    from paddle_tpu.core.lowering import cast_masters
    assert registry.AMP_SHADOW_OPS == {"grouped_matmul"}
    assert registry.AMP_SHADOW_OPS <= registry.AMP_BF16_OPS

    def shadowed(shape):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = layers.data(name="x", shape=[-1, shape[1]], dtype="float32",
                            append_batch_size=False)
            routing = layers.moe_router(x, shape[0], 2)
            out = layers.moe_experts(x, routing, shape[0], shape[2],
                                     name="ex", gated=False,
                                     activation="relu2")
            fluid.optimizer.Adam(1e-3).minimize(layers.mean(out))
        return cast_masters(main, ["ex.up.w", "ex.down.w", "nothing"])

    # OLMoE's stacks (268 MB in bf16) against Nemotron-3-Nano's (80 MB),
    # Qwen3-Next's (67 MB) and Kanana-2's (50 MB): shapes alone, no arrays
    with pytest.MonkeyPatch.context() as m:
        m.setattr(registry, "AMP_SHADOW_MIN_BYTES", 128 << 20)
        assert shadowed((64, 2048, 1024)) == ["ex.up.w", "ex.down.w"]
        for shape in ((8, 2688, 1856), (32, 2048, 512), (16, 2048, 768)):
            assert shadowed(shape) == []


def _rewritten():
    """`ex.up.w` is rewritten (halved) before the experts read it: the
    products read the cast of the rewritten value, not the shadow the step
    came in with."""
    x, y = _data(16)
    w = layers.create_parameter([4, 16, 24], "float32", name="ex.up.w")
    layers.assign(layers.scale(w, scale=0.5), output=w)
    routing = layers.moe_router(x, 4, 2, norm_topk_prob=True)
    out = layers.moe_experts(x, routing, 4, 24, name="ex", gated=False,
                             activation="relu2")
    loss = layers.mean(layers.square_error_cost(
        layers.cast(out, "float32"), y))
    return loss, ["ex.up.w", "ex.down.w"]


@pytest.mark.parametrize("optimizer", OPTIMIZERS)
def test_e_a_parameter_rewritten_mid_block_is_cast_where_it_is_read(
        optimizer):
    run = _Run(None, optimizer, build=_rewritten)
    feeds = _feeds(run.main, 3)
    run.step(feeds[0])
    assert run.handle._entry.shadow_names == run.shadowed
    for i, feed in enumerate(feeds[1:], 1):
        entry = run.handle._entry
        mut, const = entry.gather_state(run.scope)
        plain = entry._step(feed, mut, const, np.uint32(i))
        loss = run.step(feed)
        assert np.array_equal(np.asarray(plain[0][0]), np.asarray(loss))
        for name in run.shadowed:
            assert np.array_equal(np.asarray(plain[1][name]),
                                  np.asarray(run.scope.find_var(name)))
            assert _is_cast_of(run.shadows[name], run.scope.find_var(name))
    # the value the step came in with is never what the product reads,
    # and the halving (an op of its own) wrote no stale shadow
    jaxpr, masters = _traced(run, feeds[0], with_shadows=True)
    assert _converts_of(jaxpr, masters) == []
    assert run.detail["amp_plain_master_casts"] == 0


def test_e_tables_norms_routers_dense_weights_and_biases_get_none():
    def build():
        ids = layers.data(name="ids", shape=[-1, 1], dtype="int64",
                          append_batch_size=False)
        y = layers.data(name="y", shape=[-1, 16], dtype="float32",
                        append_batch_size=False)
        emb = layers.embedding(ids, size=[32, 16],
                               param_attr=fluid.ParamAttr(name="table"))
        h = layers.rms_norm(emb, param_attr=fluid.ParamAttr(name="norm.w"))
        h = layers.fc(h, 16, param_attr=fluid.ParamAttr(name="proj.w"),
                      bias_attr=fluid.ParamAttr(name="proj.b"))
        routing = layers.moe_router(
            h, 4, 2, param_attr=fluid.ParamAttr(name="router.w"))
        out = layers.moe_experts(h, routing, 4, 24, name="ex")
        return layers.mean(layers.square_error_cost(
            layers.cast(out, "float32"), y)), None

    main, startup, loss, _ = _build(None, "adam", build)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace(), amp=True)
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    exe.run(main, feed={"ids": rng.randint(0, 32, (16, 1)),
                        "y": rng.randn(16, 16).astype(np.float32)},
            fetch_list=[loss], scope=scope)
    entry = exe._handle_for(main, [loss], scope)._entry
    assert {"table", "norm.w", "proj.w", "proj.b", "router.w"} \
        <= set(entry.mut_names)
    assert entry.shadow_names == ["ex.gate.w", "ex.up.w", "ex.down.w"]
    assert entry.event.detail["amp_plain_master_casts"] == 0


# -- (f) ---------------------------------------------------------------------
@pytest.mark.parametrize("model", MODELS)
def test_f_nothing_saves_lists_or_fetches_a_shadow(model, tmp_path):
    run = _Run(model, "adam")
    run.step(_feeds(run.main, 1)[0])
    assert run.shadows
    fluid.io.save_persistables(run.exe, str(tmp_path), run.main,
                               scope=run.scope)
    persistable = {v.name for v in run.main.global_block().vars.values()
                   if v.persistable}
    saved = {f[:-len(fluid.io.PARAMS_SUFFIX)] for f in os.listdir(tmp_path)}
    assert saved == persistable
    for f in os.listdir(tmp_path):
        assert np.load(tmp_path / f).dtype != jnp.bfloat16
    held = [run.scope.find_var(n) for n in run.scope.local_var_names()]
    assert not any(getattr(v, "dtype", None) == jnp.bfloat16 for v in held)
    assert not any(v is s for v in held for s in run.shadows.values())


# -- (g) ---------------------------------------------------------------------
class _MeshRun(_Run):
    """The same program through `ParallelExecutor` on four virtual devices,
    the batch split over `dp`; with `rule` on `dp` x `mp`, the parameters
    whose names hold `rule[0]` split as `rule[1]` says."""

    def __init__(self, model, optimizer, rule=None):
        from paddle_tpu.parallel.mesh import make_mesh
        self.main, startup, self.loss, self.shadowed = _build(model,
                                                              optimizer)
        self.scope = fluid.Scope()
        fluid.Executor(fluid.CPUPlace()).run(startup, scope=self.scope)
        strategy = fluid.BuildStrategy()
        strategy.amp = True
        axes = ([2, 2], ["dp", "mp"]) if rule else ([4], ["dp"])
        if rule:
            strategy.sharding_rules.append(rule)
        self.pe = fluid.ParallelExecutor(
            loss_name=self.loss.name, main_program=self.main,
            scope=self.scope, build_strategy=strategy,
            mesh=make_mesh(*axes, jax.devices()[:4]))
        self.exe = self.pe._exe

    def step(self, feed):
        return self.pe.run(fetch_list=[self.loss.name], feed=feed)[0]


@pytest.mark.parametrize("model,optimizer", CASES)
def test_g_on_a_mesh_a_shadow_is_its_masters_cast(model, optimizer):
    run = _MeshRun(model, optimizer)
    for feed in _feeds(run.main):
        run.step(feed)
        assert sorted(run.shadows) == sorted(run.shadowed)
        for name, shadow in run.shadows.items():
            master = run.scope.find_var(name)
            assert _is_cast_of(shadow, master), name
            assert shadow.sharding.is_equivalent_to(master.sharding,
                                                    master.ndim)
    assert run.detail["amp_plain_master_casts"] == 0
    assert run.detail["amp_shadowed_params"] == len(run.shadowed)


@pytest.mark.parametrize("model,optimizer", CASES)
def test_g_on_a_mesh_losses_are_those_of_a_regathering_run(model,
                                                           optimizer):
    carried, regathered = _MeshRun(model, optimizer), _MeshRun(model,
                                                               optimizer)
    for feed in _feeds(carried.main):
        a = carried.step(feed)
        regathered.scope.set_var("unrelated", np.zeros(1, np.float32))
        assert np.array_equal(np.asarray(a),
                              np.asarray(regathered.step(feed)))


def test_g_a_shadow_takes_its_masters_sharding():
    # momentum: the stack's only other state is its velocity, of its shape
    run = _MeshRun("experts", "momentum",
                   rule=("ex.up.w", (None, None, "mp")))
    for feed in _feeds(run.main, 3):
        run.step(feed)
        master, shadow = run.scope.find_var("ex.up.w"), \
            run.shadows["ex.up.w"]
        assert not master.sharding.is_fully_replicated
        assert shadow.sharding.is_equivalent_to(master.sharding, 3)
        assert _is_cast_of(shadow, master)
    # the compiled text of the running step: lowered on the shadows'
    # signature, with the shard of `ex.up.w` carried in bf16
    text = run.pe.compiled_text(_feeds(run.main, 1)[0])
    assert "bf16[4,16,12]" in text
