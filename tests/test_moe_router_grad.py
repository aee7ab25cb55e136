"""`moe_router`'s registered grad (`ops/moe.py::_moe_router_grad`): built from
the forward's saved `Probs` and `TopKIndex`, against `jax.vjp` of the rule
itself over every attribute combination the rule takes; the lowered grad op
of a built Program holds no scatter, `top_k` or sort, and counts itself on
the compile event."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observe
from paddle_tpu.core import lowering, registry
from paddle_tpu.ops import moe

TOKENS, WIDTH, EXPERTS, K = 64, 12, 16, 3
SLOTS = ("TopKWeight", "TopKIndex", "TokensPerExpert", "Probs", "LogSumExp")
# Not bitwise: the generic vjp sums the softmax's and the renormalisation's
# terms in the order `jax.vjp` transposes them (d(raw / total) as two
# products, the softmax through `exp(logits - lse)` and logsumexp's own
# rule), the registered grad in the closed forms. Both are float32 sums of
# the same terms; they differ by roundings, 2e-6 of the largest entry here.
RTOL = 1e-5
# a bf16 dX is either side's float32 value rounded once: one bf16 place
RTOL_BF16 = 2.0 ** -8

NORMS = {"plain": {}, "norm": {"norm_topk_prob": True},
         "norm_eps": {"norm_topk_prob": True, "norm_eps": 1e-2}}
SCALES = {"unscaled": {}, "scaled": {"scaling_factor": 2.5}}
GROUPS = {"one_group": {}, "two_of_four": {"n_group": 4, "topk_group": 2}}


def _ctx(attrs, fwd_outs=None):
    """A rule's context outside a program: its attributes, no lowerer (so
    `tally` writes nothing)."""
    ctx = registry.LoweringContext(attrs)
    ctx.fwd_outs = fwd_outs
    return ctx


def _operands(dtype, seed=0):
    """Rows with tied scores included: three rows of zeros (every expert
    ties with every other, whatever the score) and two experts with one
    column (they tie in every row)."""
    rng = np.random.RandomState(seed)
    x = rng.randn(TOKENS, WIDTH).astype(np.float32)
    x[[5, 17, 40]] = 0.0
    w = rng.randn(WIDTH, EXPERTS).astype(np.float32)
    w[:, 9] = w[:, 2]
    b = (rng.randn(EXPERTS) * 0.3).astype(np.float32)
    cot = {"TopKWeight": rng.randn(TOKENS, K).astype(np.float32),
           "Probs": rng.randn(TOKENS, EXPERTS).astype(np.float32),
           "LogSumExp": rng.randn(TOKENS).astype(np.float32)}
    return jnp.asarray(x, dtype), jnp.asarray(w), jnp.asarray(b), cot


def _both(attrs, x, w, b, cot, given):
    """(dX, dW) of the registered grad and of `jax.vjp` of the rule, under
    the cotangents named in `given` (the others absent, as a program without
    that loss hands them: `None` there, zeros here)."""
    def rule(x, w):
        outs = moe._moe_router(_ctx(attrs), x, w, b)
        return tuple(outs[s] for s in SLOTS)

    primals, vjp = jax.vjp(rule, x, w)
    zeros = [np.zeros(p.shape, jax.dtypes.float0)
             if not jnp.issubdtype(p.dtype, jnp.floating) else jnp.zeros_like(p)
             for p in primals]
    want = vjp(tuple(jnp.asarray(cot[s]) if s in given and s in cot else z
                     for s, z in zip(SLOTS, zeros)))
    ins = {"X": [x], "W": [w]}
    if b is not None:
        ins["Bias"] = [b]
    got = moe._moe_router_grad(
        _ctx(attrs, {s: [p] for s, p in zip(SLOTS, primals)}), ins,
        {s: [jnp.asarray(cot[s]) if s in given and s in cot else None]
         for s in SLOTS})
    return got, want, primals


def _close(got, want, rtol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= rtol * np.abs(want).max()


VARIANTS = list(itertools.product(
    ("softmax", "sigmoid"), ("nobias", "bias"), sorted(NORMS), sorted(SCALES),
    sorted(GROUPS), ("weights_only", "with_losses"), ("float32", "bfloat16")))


@pytest.mark.parametrize("score,bias,norm,scale,groups,losses,dtype",
                         VARIANTS, ids=["-".join(v) for v in VARIANTS])
def test_registered_grad_against_vjp_of_the_rule(score, bias, norm, scale,
                                                 groups, losses, dtype):
    attrs = {"k": K, "score_func": score, **NORMS[norm], **SCALES[scale],
             **GROUPS[groups]}
    x, w, b, cot = _operands(jnp.dtype(dtype))
    given = SLOTS if losses == "with_losses" else ("TopKWeight",)
    got, (want_x, want_w), primals = _both(
        attrs, x, w, b if bias == "bias" else None, cot, given)
    index = np.asarray(primals[1])
    assert all(len(set(row)) == K for row in index)
    assert got["X"].dtype == x.dtype and got["W"].dtype == w.dtype
    assert np.abs(np.asarray(want_w)).max() > 0
    _close(got["X"], want_x, RTOL if dtype == "float32" else RTOL_BF16)
    _close(got["W"], want_w, RTOL)
    if bias == "bias":
        assert not np.asarray(got["Bias"]).any()


@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
@pytest.mark.parametrize("given", [("Probs",), ("LogSumExp",),
                                   ("Probs", "LogSumExp")],
                         ids=["probs", "lse", "probs_lse"])
def test_a_loss_alone_reaches_the_router(score, given):
    """No cotangent for the weights (a probe that reads a router loss
    alone): the chosen experts add nothing, the score's backward stands."""
    attrs = {"k": K, "score_func": score, "norm_topk_prob": True}
    x, w, b, cot = _operands(jnp.float32, seed=1)
    got, (want_x, want_w), _ = _both(attrs, x, w, b, cot, given)
    _close(got["X"], want_x, RTOL)
    _close(got["W"], want_w, RTOL)


def test_no_cotangent_no_gradient():
    x, w, b, cot = _operands(jnp.float32)
    got, _, _ = _both({"k": K}, x, w, b, cot, ())
    assert got == {}


# -- the grad op of a built Program -------------------------------------------------

ROUTERS = (dict(score_func="softmax"),
           dict(score_func="sigmoid", norm_topk_prob=True, norm_eps=1e-20,
                scaling_factor=2.5, n_group=4, topk_group=2,
                bias_attr=fluid.ParamAttr(name="b")))


def _program():
    """Two routers of one input, each with both router losses, and their
    backward."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[TOKENS, WIDTH], dtype="float32",
                        append_batch_size=False, stop_gradient=False)
        parts = []
        for i, kw in enumerate(ROUTERS):
            routing = layers.moe_router(
                x, EXPERTS, K, param_attr=fluid.ParamAttr(name=f"w{i}"),
                **kw)
            parts += [layers.reduce_sum(layers.square(routing[key]))
                      for key in ("weight", "probs", "logsumexp")]
        loss = layers.sums(parts)
        fluid.append_backward(loss)
    return main, startup, loss


def _run(main, startup, loss):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(3)
    scope.set_var("b", jnp.asarray(rng.randn(EXPERTS) * 0.3, jnp.float32))
    x = rng.randn(TOKENS, WIDTH).astype(np.float32)
    return exe.run(main, feed={"x": x}, scope=scope,
                   fetch_list=[loss, "x@GRAD", "w0@GRAD", "w1@GRAD"])


def test_program_grads_match_the_generic_vjp_path(monkeypatch):
    """The same Program lowered with the registered grad and, the
    registration taken away, through `_run_grad_op`'s generic branch: one
    loss bit for bit (the forward rule is not touched), the gradients to
    float32 roundings; the compile event counts the program's routers."""
    got = _run(*_program())
    main, startup, loss = _program()
    monkeypatch.setattr(registry.get_op_def("moe_router"), "grad_lower", None)
    want = _run(main, startup, loss)
    assert "moe_router_direct_grads" not in \
        observe.observatory().latest(main._uid).detail
    assert np.array_equal(got[0], want[0])
    for g, w in zip(got[1:], want[1:]):
        _close(g, w, RTOL)


def test_lowered_grad_op_holds_no_scatter_sort_or_top_k():
    """Each `moe_router_grad` op of the Program lowered alone, through the
    block lowerer, on its inputs' shapes: no `scatter`, `top_k` or `sort` in
    its StableHLO (the forward op's text, lowered the same way, has the
    `top_k`s: the reading can see one). And the step's compile event
    carries `moe_router_direct_grads` equal to the program's routers."""
    main, startup, loss = _program()
    block = main.global_block()

    def text_of(idx, op):
        names = sorted(set(op.input_arg_names)
                       | {n for ns in op.attrs.get(
                           registry.FWD_OP_ATTR, {"outputs": {}})["outputs"]
                           .values() for n in ns})
        avals = {n: jax.ShapeDtypeStruct(block.var(n).shape,
                                         jnp.dtype(block.var(n).dtype))
                 for n in names}
        lowerer = lowering.BlockLowerer(main)

        def run(env):
            env = dict(env)
            lowerer._run_op(block, op, idx, env, jax.random.PRNGKey(0))
            return [env[n] for n in op.output_arg_names]
        return jax.jit(run).lower(avals).as_text(), lowerer.detail

    seen = 0
    for idx, op in enumerate(block.ops):
        if op.type == "moe_router":
            assert "top_k" in text_of(idx, op)[0]
        if op.type != "moe_router_grad":
            continue
        seen += 1
        text, detail = text_of(idx, op)
        assert "dot_general" in text
        # by op name: a gather carries an `indices_are_sorted` attribute
        for word in ("stablehlo.scatter", "top_k", "stablehlo.sort"):
            assert word not in text, word
        assert detail["moe_router_direct_grads"] == 1
    assert seen == len(ROUTERS)
    _run(main, startup, loss)
    assert observe.observatory().latest(main._uid) \
        .detail["moe_router_direct_grads"] == len(ROUTERS)
