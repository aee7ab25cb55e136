"""Ouro (a looped LM: one stack of layers applied `n_loop` times under
shared weights, an exit gate, the expected-loss objective) through `layers`
-> Program IR -> `Executor`, against the plain reference
(`tests/ouro_reference.py`). Seeded random weights, float32, AMP off unless
a test says otherwise."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models, observe
from paddle_tpu.core import ir
from paddle_tpu.observe.census import parameter_sharing

import ouro_reference as ref
from decoder_case import DecoderCase, tiny_args

TINY = tiny_args("ouro")
REF_KW = dict(n_layer=2, n_head=2, n_loop=4)
# float32 against float32 highest: the two sides differ by the order of
# their sums (the flash path and the einsum; log-space exit probabilities
# and the running product), a few ulp of 6e-8 each
RTOL = 1e-5
SCALARS = ["loss", "expected_ce", "entropy"]
LAYER_PARAMS = ("attn_norm", "q", "k", "v", "o", "attn_post_norm",
                "mlp_norm", "gate", "up", "down", "mlp_post_norm")
PARAM_NAMES = (["embed.w", "final_norm.w", "head.w", "exit_gate.w",
                "exit_gate.b"]
               + [f"l{i}.{n}.w" for i in range(TINY["n_layer"])
                  for n in LAYER_PARAMS])


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    got = got.reshape(want.shape)
    return np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30)


def _seeded_values(shapes, seed=3):
    """Weights far from their initial values, so that no term of the
    comparison is small by construction: norm weights in [0.5, 1.5], a gate
    that is not at 1/2, matrices of std 0.1 (five times the initial), gate 0.5."""
    rng = np.random.RandomState(seed)
    values = {}
    for name in sorted(shapes):
        shape = shapes[name]
        if "norm" in name:
            value = rng.uniform(0.5, 1.5, shape)
        elif name.startswith("exit_gate"):
            value = rng.randn(*shape) * 0.5
        else:
            value = rng.randn(*shape) * 0.1
        values[name] = value.astype(np.float32)
    return values


def _head_outputs(main):
    """The logits of every pass: what the products with `head.w` write."""
    return [op.output("Out")[0] for op in main.global_block().ops
            if op.type == "mul" and "head.w" in op.input_arg_names]


# a gradient is held entry by entry, by this file's `rel_err` (it reshapes: a
# fetched scalar is `[1]`, the reference's `[]`), not in the Frobenius norm
CASE = DecoderCase(
    models.ouro.build, TINY, ref, REF_KW, SCALARS + ["exit_probs"],
    seeded_values=_seeded_values, interpreted=True, out_tol=RTOL,
    grad_tol=RTOL, grad_err=rel_err,
    also_fetch=lambda main: {"logits": _head_outputs(main)})


@pytest.fixture(scope="module")
def tiny():
    """The tiny model through the chip's flash kernels, interpreted on the
    CPU."""
    return CASE.tiny_model()


# -- the program: weights shared, not copied -------------------------------------

def test_the_scope_holds_one_set_of_layer_weights_for_all_passes(tiny):
    CASE.has_the_reference_parameters(tiny, PARAM_NAMES)


def test_one_adam_op_a_parameter_and_a_fanin_of_the_loop_count():
    main, _, _, _ = CASE.program(fluid.optimizer.Adam(learning_rate=1e-3))
    block = main.global_block()
    adam = [op.input("Param")[0] for op in block.ops if op.type == "adam"]
    assert sorted(adam) == sorted(PARAM_NAMES)
    sums = {op.output("Out")[0]: len(op.input("X")) for op in block.ops
            if op.type == "sum" and op.attrs.get("__role__") == "backward"}
    for name in PARAM_NAMES:
        # the embedding is read once (no sum op); the gate is not built
        # after the last pass: three uses
        want = None if name == "embed.w" else \
            3 if name.startswith("exit_gate") else 4
        assert sums.get(name + "@GRAD") == want, name
    shared = parameter_sharing(main)
    assert shared == {"parameters": len(PARAM_NAMES),
                      "parameter_uses": 4 * len(PARAM_NAMES) - 3 - 2,
                      "grad_fanin_max": 4}      # embed.w is read once


def test_compile_event_carries_the_sharing_counters():
    main, startup, fetches, _ = CASE.program(
        fluid.optimizer.SGD(learning_rate=1e-3), n_layer=1, n_loop=2,
        seq_len=16)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = {k: v[:, :16] for k, v in CASE.batch().items()}
    exe.run(main, feed=feed, fetch_list=[fetches["loss"]], scope=scope)
    detail = observe.observatory().latest(main._uid).detail
    assert detail["grad_fanin_max"] == 2
    assert detail["parameters"] == 11 + 5
    assert detail["parameter_uses"] == 2 * 11 + 2 + 2 + 2 + 1
    # the startup program has parameters and no backward pass
    assert observe.observatory().latest(startup._uid).detail[
        "grad_fanin_max"] == 0


def test_a_program_without_sharing_reads_a_fanin_of_one():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        loss = layers.mean(layers.fc(layers.fc(x, size=3), size=1))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    assert parameter_sharing(main) == {"parameters": 4, "parameter_uses": 4,
                                       "grad_fanin_max": 1}


def test_every_pass_is_built_under_its_name_scope(tiny):
    block = tiny["main"].global_block()
    scopes = [op.attrs.get(ir.NAME_SCOPE_ATTR) for op in block.ops
              if op.type == "fused_attention"]
    assert scopes == [f"ut_step{t}" for t in (1, 2, 3, 4) for _ in range(2)]
    grad_scopes = {op.attrs["__fwd_op__"]["attrs"][ir.NAME_SCOPE_ATTR]
                   for op in block.ops if op.type == "fused_attention_grad"}
    assert grad_scopes == {f"ut_step{t}" for t in (1, 2, 3, 4)}
    assert ir.NAME_SCOPE_ATTR not in block.ops[0].attrs     # the embedding


def test_name_scope_reaches_the_lowered_text_and_nests():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        with fluid.name_scope("outer"):
            h = layers.scale(x, scale=2.0)
            with fluid.name_scope("inner"):
                h = layers.exp(h)
        out = layers.mean(h)
    ops = main.global_block().ops
    assert [op.attrs.get(ir.NAME_SCOPE_ATTR) for op in ops] == \
        ["outer", "outer/inner", None]
    from paddle_tpu.core.lowering import BlockLowerer

    def run(x):
        env = {"x": x}
        BlockLowerer(main).run_block(0, env, jax.random.key(0))
        return env[out.name]
    text = jax.jit(run).lower(jnp.ones((2, 4))).as_text(debug_info=True)
    assert "outer/inner/exp" in text and "outer/scale" in text


# -- the system against the reference -------------------------------------------

@pytest.mark.parametrize("name", SCALARS + ["exit_probs"])
def test_tiny_model_output_matches_reference(tiny, name):
    CASE.output_matches_reference(tiny, name)


@pytest.mark.parametrize("step", [1, 2, 3, 4])
def test_tiny_model_logits_of_every_pass_match_reference(tiny, step):
    assert len(tiny["got"]["logits"]) == 4
    assert rel_err(tiny["got"]["logits"][step - 1],
                   tiny["want"]["logits"][step - 1]) < RTOL


def test_exit_probabilities_sum_to_one_and_are_not_uniform(tiny):
    probs = np.asarray(tiny["got"]["exit_probs"])
    assert probs.shape == (4,) and np.all(probs > 0)
    np.testing.assert_allclose(probs.sum(), 1.0, rtol=1e-6)
    assert probs.max() - probs.min() > 0.05
    # loss = expected_ce - beta * entropy, beta 0.1
    got = tiny["got"]
    np.testing.assert_allclose(
        float(got["loss"][0]),
        float(got["expected_ce"][0]) - 0.1 * float(got["entropy"][0]),
        rtol=1e-6)
    # the expected loss lies between the best and the worst pass's
    ce = np.asarray(tiny["want"]["ce"])
    assert ce.min() < float(got["expected_ce"][0]) < ce.max()


@pytest.mark.parametrize("name", PARAM_NAMES)
def test_tiny_model_gradient_matches_reference(tiny, name):
    CASE.gradient_matches_reference(tiny, name)


@pytest.mark.parametrize("name", ["l0.q.w", "l1.down.w",
                                  "l0.attn_post_norm.w", "final_norm.w",
                                  "head.w", "exit_gate.w"])
def test_shared_gradient_is_the_sum_over_an_untied_twin(tiny, name):
    """The same program with every pass reading a parameter of its own
    (`<name>@ut_step<t>`, loaded with the shared value): its loss is the
    shared program's, and the shared weight's gradient is the sum of the
    twin's gradients over the passes."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        _, fetches = models.ouro.build(**TINY)
        block = main.global_block()
        shared = block.var(name)
        twins = []
        for op in block.ops:
            step = op.attrs.get(ir.NAME_SCOPE_ATTR)
            for names in op.inputs.values():
                if name in names:
                    twin = f"{name}@{step}"
                    block.create_parameter(twin, shared.shape, shared.dtype)
                    names[names.index(name)] = twin
                    twins.append(twin)
        fluid.append_backward(fetches["loss"])
    assert len(twins) == (3 if name == "exit_gate.w" else 4)
    scope = fluid.Scope()
    for n, v in tiny["params"].items():
        scope.set_var(n, jnp.asarray(v))
    for twin in twins:
        scope.set_var(twin, jnp.asarray(tiny["params"][name]))
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        out = fluid.Executor(fluid.CPUPlace()).run(
            main, feed=tiny["feed"],
            fetch_list=[fetches["loss"]] + [t + "@GRAD" for t in twins],
            scope=scope)
    assert rel_err(out[0], tiny["got"]["loss"]) < 1e-6
    per_pass = [np.asarray(g, np.float64) for g in out[1:]]
    assert rel_err(tiny["grads"][name], sum(per_pass)) < RTOL
    # a sum that dropped any one pass would not pass
    for g in per_pass:
        assert rel_err(tiny["grads"][name], sum(per_pass) - g) > 100 * RTOL


def test_one_pass_without_a_gate_is_a_plain_decoder():
    """`n_loop=1`: p_1 = 1, no gate is built, the entropy is 0 and the loss
    is the mean cross-entropy of a plain pre- and post-norm decoder."""
    main, params, feed, got, grads, _ = CASE.run_tiny(
        amp=False, batch_seed=1, n_loop=1)
    assert not [n for n in params if n.startswith("exit_gate")]
    assert parameter_sharing(main)["grad_fanin_max"] == 1
    loss, ce, entropy, probs = (got[n] for n in SCALARS + ["exit_probs"])
    grad = grads["l1.up.w"]
    with jax.default_matmul_precision("highest"):
        (g,), p = ref.passes(params, feed["tokens"], n_layer=2, n_head=2,
                             n_loop=1)
        want = jnp.mean(ref.head_ce(g, p["head.w"], feed["labels"]))
    assert rel_err(loss, want) < RTOL and rel_err(ce, want) < RTOL
    assert float(entropy[0]) == 0.0 and float(probs[0]) == 1.0
    _, want_grads = ref.loss_and_grads(params, feed["tokens"],
                                       feed["labels"], wrt=["l1.up.w"],
                                       n_layer=2, n_head=2, n_loop=1)
    assert rel_err(grad, want_grads["l1.up.w"]) < RTOL


# its own body: every scalar, the exit distribution and every pass's logits
# are compared, at the last 16 positions
def test_reference_in_blocks_is_the_reference(tiny):
    """`q_block` and `remat` are the reference's memory at published
    widths, not its mathematics: they change the order of a few float32
    sums and nothing else."""
    parts, grads = ref.loss_and_grads(
        tiny["params"], tiny["feed"]["tokens"], tiny["feed"]["labels"],
        wrt=["l0.k.w", "head.w"], last=16, q_block=32, remat=True, **REF_KW)
    want = tiny["want"]
    for name in SCALARS + ["exit_probs", "ce"]:
        assert rel_err(parts[name], want[name]) < RTOL, name
    assert rel_err(parts["logits"],
                   np.asarray(want["logits"])[:, :, -16:]) < RTOL
    for name in grads:
        assert rel_err(grads[name], tiny["want_grads"][name]) < RTOL, name


def test_reference_exit_distribution_by_hand():
    z = [jnp.asarray([0.0, 2.0]), jnp.asarray([0.0, -1.0])]
    p = np.asarray(ref.exit_distribution(z))
    s = lambda v: 1 / (1 + np.exp(-v))
    np.testing.assert_allclose(p[:, 0], [0.5, 0.25, 0.25], rtol=1e-6)
    np.testing.assert_allclose(
        p[:, 1], [s(2.0), (1 - s(2.0)) * s(-1.0),
                  (1 - s(2.0)) * (1 - s(-1.0))], rtol=1e-6)


# its own body: the seeded weights against `tiny`'s reference, every pass's
# logits and the exit distribution
def test_tiny_model_amp_within_bf16_of_reference(tiny):
    """AMP on: projections, attention and the head in bf16 (relative
    rounding 2^-9 an operand); the gate's logit, the exit distribution, the
    cross-entropies and the entropy in float32. Logits are sums of 64
    products of O(1) terms and move by about 0.05 on a std of 2.4; a loss is
    a mean over 256 positions and moves far less (read: logits 0.06, loss
    parts 1e-4 to 2e-3, exit_probs 3e-4, gradients 0.6-1.3% in the
    Frobenius norm)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        _, _, _, got, grads, _ = CASE.run_tiny(amp=True)
    want = tiny["want"]
    for step in range(4):
        assert got["logits"][step].dtype == jnp.bfloat16
        assert np.max(np.abs(np.asarray(got["logits"][step], np.float32)
                             - np.asarray(want["logits"][step]))) < 0.15
    for name in SCALARS:
        assert got[name].dtype == np.float32
        assert abs(float(got[name][0]) - float(want[name])) < 5e-3, name
    assert got["exit_probs"].dtype == np.float32
    assert np.max(np.abs(got["exit_probs"]
                         - np.asarray(want["exit_probs"]))) < 2e-3
    for name in ("head.w", "l0.q.w", "l1.mlp_post_norm.w", "exit_gate.w",
                 "embed.w"):
        assert grads[name].dtype == np.float32
        want_grad = np.asarray(tiny["want_grads"][name], np.float64)
        assert (np.linalg.norm(grads[name].reshape(want_grad.shape)
                               - want_grad)
                < 0.05 * np.linalg.norm(want_grad)), name


def test_five_adam_steps_lower_the_loss():
    CASE.adam_steps_lower_the_loss(seed=5, steps=5)


def test_the_two_copies_of_the_reference_are_identical():
    CASE.two_copies_of_the_reference_are_identical()
