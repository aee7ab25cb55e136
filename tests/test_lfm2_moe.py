"""LFM2-MoE (gated short convolutions of three taps with NO activation beside
QK-normed rotary attention, a leading dense gated MLP, then one chip's share
of routed experts with no shared expert under a sigmoid router with a
selection bias, one table as embedding and head) through `layers` -> Program
IR -> `Executor`, against the plain reference (`tests/lfm2_moe_reference.py`:
the convolution as shifted products, the gates as written, `jnp.repeat`, a
loop over the held experts, the table used twice). The sizes are the
configuration's `tiny` block. Seeded random weights, float32, AMP off unless a
test says otherwise.

Tolerances: a float32 program against a float32 reference at "highest" agrees
to a few 1e-6 in a product's result; through five layers of two sublayers,
a softmax and a top-k the logits stay within 1e-4 of their largest value and a
gradient within 2e-4 in the Frobenius norm (`test_trinity.py`'s limits, for
its reason). A piece alone: 2e-5 of the largest value (RTOL)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models
from paddle_tpu.core import registry
from paddle_tpu.observe import census
from paddle_tpu.ops import linear_attention as la

import lfm2_moe_reference as ref
from decoder_case import (DecoderCase, _forward_ops_by_scope, _planted,
                          build_program, carries_the_census, config, frob,
                          piece_noted, rel_err, run_piece,
                          runs_through_the_benchmark, tiny_args)

CONFIG = config("lfm2_moe")
GAMMA = 0.001
TYPES = ["conv", "full_attention", "conv", "conv", "conv"]
PUBLISHED = range(1, 6)         # the cut's layers by their published index
# the model's own layers 1-5, hidden 64, a dense MLP of 96, 4/2 heads of 16,
# 128 tokens, 16 experts of 32 at top-4 of which 4 are held from expert 0
TINY = tiny_args("lfm2_moe")
REF_KW = {k: TINY[k] for k in (
    "layer_types", "first_layer", "n_head", "n_kv_head", "head_dim",
    "rope_theta", "top_k", "first_expert", "route_scale", "route_norm_eps",
    "tie_embeddings", "rms_eps")}
RTOL = 2e-5


def test_the_tiny_block_is_the_issues():
    assert TINY["layer_types"] == TYPES == CONFIG["layer_types"][1:6]
    assert (TINY["first_layer"], TINY["n_dense_layer"]) == (1, 2)
    assert (TINY["seq_len"], TINY["d_model"], TINY["d_dense"],
            TINY["conv_taps"]) == (128, 64, 96, 3)
    assert (TINY["n_head"], TINY["n_kv_head"], TINY["head_dim"]) == (4, 2, 16)
    assert (TINY["n_expert"], TINY["top_k"], TINY["d_expert"],
            TINY["experts_held"], TINY["first_expert"]) == (16, 4, 32, 4, 0)
    assert (TINY["route_scale"], TINY["route_norm_eps"], TINY["rope_theta"],
            TINY["rms_eps"]) == (1.0, 1e-6, 1e6, 1e-5)
    # every size that sets the cost is overridden; what stays is no size
    kept = set(CONFIG["build_args"]) - set(CONFIG["tiny"]["build_args"])
    assert kept == {"layer_types", "first_layer", "n_dense_layer",
                    "conv_taps", "rope_theta", "route_scale",
                    "route_norm_eps", "bias_update_rate", "first_expert",
                    "tie_embeddings", "rms_eps"}


# -- the convolution without an activation -------------------------------------------------------

def _conv_layer(taps, activation):
    return lambda d: [layers.causal_conv1d(
        d["x"], taps, activation=activation,
        param_attr=fluid.ParamAttr(name="w"))]


@pytest.mark.parametrize("activation", [None, "silu"])
def test_causal_conv1d_takes_its_activation(activation):
    """`layers.causal_conv1d(activation=None)`: three causal taps a channel
    and nothing after them; forward, dX and dW against `_conv_xla` and its
    `jax.vjp` (off the lane tile: the XLA form), and the default is silu as
    it was."""
    rng = np.random.RandomState(0)
    x = rng.randn(2, 24, 12).astype(np.float32)
    w = rng.uniform(-0.6, 0.6, (12, 3)).astype(np.float32)
    assert la._conv_plan(24, 12, 3) == "xla"
    (y,), grads, probe = run_piece(_conv_layer(3, activation), {"x": x},
                                   {"w": w})
    assert piece_noted("causal_conv_plan") == "xla"
    silu = activation == "silu"
    want, vjp = jax.vjp(lambda a, b: la._conv_xla(a, b, silu), x, w)
    assert rel_err(y, want) < RTOL
    gx, gw = vjp(jnp.asarray(probe))
    assert rel_err(grads["x"], gx) < RTOL and rel_err(grads["w"], gw) < RTOL
    # and it is the reference's sum of shifted products
    bare = ref.causal_conv(jnp.asarray(x), jnp.asarray(w))
    assert (rel_err(y, bare) < RTOL) == (not silu)
    # the first token reads zeros before it
    assert np.allclose(np.asarray(bare)[:, 0], x[:, 0] * w[:, 2], atol=1e-6)


def test_the_layer_writes_the_activation_the_op_reads():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = layers.data(name="x", shape=[1, 16, 8], dtype="float32",
                        append_batch_size=False)
        layers.causal_conv1d(x, 3, activation=None)
        layers.causal_conv1d(x, 4)
        with pytest.raises(ValueError, match="activation is"):
            layers.causal_conv1d(x, 3, activation="relu")
    convs = [o for o in main.global_block().ops if o.type == "causal_conv1d"]
    assert [o.attrs["activation"] for o in convs] == ["", "silu"]


def test_interpreted_kernels_at_three_taps_without_silu(monkeypatch):
    """`causal_conv_fwd` / `causal_conv_bwd` under the Pallas interpreter at
    3 taps with no silu and no bias, 256 channels over two time blocks'
    worth of rows, against `_conv_xla` and its `jax.vjp`: forward, dX and
    dW."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert la._conv_plan(96, 256, 3) == "kernel"
    assert la._conv_kernels_run(96, 256, 3)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 96, 256), jnp.float32)
    w = jnp.asarray(rng.uniform(-0.6, 0.6, (256, 3)), jnp.float32)
    d_out = jnp.asarray(rng.randn(2, 96, 256), jnp.float32)
    want, vjp = jax.vjp(lambda a, b: la._conv_xla(a, b, False), x, w)
    assert rel_err(la._conv_forward(x, w, False), want) < RTOL
    dx, dw = la._conv_backward(x, w, d_out, False)
    gx, gw = vjp(d_out)
    assert rel_err(dx, gx) < RTOL and rel_err(dw, gw) < RTOL
    # with silu it is another function
    assert rel_err(la._conv_forward(x, w, True), want) > 0.1
    # and the op takes the kernels there, and says so
    (y,), grads, probe = run_piece(
        _conv_layer(3, None), {"x": np.asarray(x)}, {"w": np.asarray(w)})
    assert piece_noted("causal_conv_plan") == "kernel"
    assert rel_err(y, want) < RTOL
    gx, gw = vjp(jnp.asarray(probe))
    assert rel_err(grads["x"], gx) < RTOL and rel_err(grads["w"], gw) < RTOL


def test_the_published_shape_is_on_the_kernels_plan():
    assert la._conv_plan(4096, 2048, 3) == "kernel"
    assert la._conv_blocks(4096, 2048) == (2048, 256, 64)


# -- the router's epsilon ------------------------------------------------------------------------

def _route(x, w, b, eps):
    def build(d):
        router = models._decoder.noaux_router("r", GAMMA, 1.0) \
            if eps is None else models._decoder.noaux_router(
                "r", GAMMA, 1.0, norm_eps=eps)
        r = layers.moe_router(
            d["x"], w.shape[1], 4, param_attr=fluid.ParamAttr(name="w"),
            **{**router, "bias_attr": _planted("r.router.bias", b)})
        return [r["weight"], r["index"]]
    return run_piece(build, {"x": x}, {"w": w})


@pytest.mark.parametrize("eps", [None, 1e-6, 0.5])
def test_noaux_router_takes_its_epsilon(eps):
    """`noaux_router(norm_eps=)`: w = s[idx] / (sum + eps) against the closed
    form and its gradient; the default is the 1e-20 it was."""
    rng = np.random.RandomState(2)
    x = rng.randn(40, 16).astype(np.float32)
    w = rng.randn(16, 8).astype(np.float32)
    b = (rng.randn(8) * 0.3).astype(np.float32)
    (weight, index), grads, probe = _route(x, w, b, eps)
    used = 1e-20 if eps is None else eps
    with jax.default_matmul_precision("highest"):
        want, want_index, _ = ref.route(x, w, b, 4, 1.0, used)
        gx, gw = jax.grad(lambda a, c: jnp.sum(
            ref.route(a, c, b, 4, 1.0, used)[0] * probe), (0, 1))(x, w)
    assert np.array_equal(index, np.asarray(want_index))
    assert rel_err(weight, want) < RTOL
    assert rel_err(grads["x"], gx) < 1e-4 and rel_err(grads["w"], gw) < 1e-4
    sums = np.asarray(weight).sum(1)
    if used < 1e-3:
        assert np.allclose(sums, 1.0, atol=1e-5)
    else:
        assert np.all(sums < 0.9)


def test_the_default_router_keywords_are_what_they_were():
    got = models._decoder.noaux_router("l1", 0.001, 2.5, 8, 4)
    assert got["norm_eps"] == 1e-20 and got["scaling_factor"] == 2.5
    assert (got["n_group"], got["topk_group"]) == (8, 4)
    assert models._decoder.noaux_router("l1", 0.001, 1.0, norm_eps=1e-6)[
        "norm_eps"] == 1e-6


# -- the shares add up ---------------------------------------------------------------------------

def test_the_shares_add_up_to_the_whole_layer():
    """8 experts of which 2 are held by each of 4 shares: the four shares'
    expert-layer outputs sum to the uncut reference's for the whole layer
    (there is no shared expert, so nothing is counted once): forward, the
    gradient of the router and of the layer's input. With a planted non-zero
    `b`, so that choosing by `s + b` and weighting by `s` cannot be
    confused."""
    n_expert, held, k, width, d = 8, 2, 4, 12, 16
    rng = np.random.RandomState(5)
    x = rng.randn(40, d).astype(np.float32)
    whole = {"router.w": rng.randn(d, n_expert),
             "router.bias": rng.randn(n_expert) * 0.3,
             "experts.gate.w": rng.randn(n_expert, d, width) * 0.3,
             "experts.up.w": rng.randn(n_expert, d, width) * 0.3,
             "experts.down.w": rng.randn(n_expert, width, d) * 0.3}
    whole = {n: v.astype(np.float32) for n, v in whole.items()}
    shares = n_expert // held
    cut = {f"s{j}.{which}.w":
           whole[f"experts.{which}.w"][j * held:(j + 1) * held]
           for j in range(shares) for which in ("gate", "up", "down")}

    def build(data):
        router = models._decoder.noaux_router("l", GAMMA, 1.0, norm_eps=1e-6)
        routing = layers.moe_router(
            data["x"], n_expert, k, param_attr=fluid.ParamAttr(name="router.w"),
            **{**router, "bias_attr": _planted("l.router.bias",
                                               whole["router.bias"])})
        parts = [layers.moe_experts(
            data["x"], routing, n_expert, width, name=f"s{j}",
            first_expert=j * held, experts_held=held) for j in range(shares)]
        return [layers.sums(parts)] + parts

    params = {"router.w": whole["router.w"], **cut}
    outs, grads, probe = run_piece(build, {"x": x}, params)
    kw = dict(top_k=k, route_scale=1.0, norm_eps=1e-6)

    def want(x, router_w):
        return ref.routed_experts({**whole, "router.w": router_w}, x,
                                  first_expert=0, **kw)[0]

    with jax.default_matmul_precision("highest"):
        assert rel_err(outs[0], want(x, whole["router.w"])) < RTOL
        gx, gr = jax.grad(lambda a, b: jnp.sum(want(a, b) * probe),
                          (0, 1))(x, whole["router.w"])
        for j in range(shares):     # a share alone is the reference given it
            own = {n: (v[j * held:(j + 1) * held]
                       if n.startswith("experts.") else v)
                   for n, v in whole.items()}
            alone = ref.routed_experts(own, x, first_expert=j * held, **kw)[0]
            assert rel_err(outs[1 + j], alone) < 1e-4, j
            assert rel_err(outs[1 + j], want(x, whole["router.w"])) > 0.1
        # the bias mattered: at b = 0 the layer is another function
        unbiased = ref.routed_experts(
            {**whole, "router.bias": np.zeros(n_expert, np.float32)}, x,
            first_expert=0, **kw)[0]
        assert rel_err(unbiased, want(x, whole["router.w"])) > 0.05
    assert rel_err(grads["x"], gx) < 1e-4
    assert rel_err(grads["router.w"], gr) < 1e-4


# -- the model -----------------------------------------------------------------------------------

def _seeded_values(shapes, seed=3):
    """Weights far from their initial values, so that no term of the
    comparison is small by construction: norm weights in [0.5, 1.5] (the
    query norm's twice that, so the softmax is sharp), a router five times as
    sharp, a planted bias of std 0.2 (the sigmoids' spread is about 0.25),
    the three taps of a convolution times 0.5, 1 and 2 (exchangeable taps
    would make their order no fault in distribution), query, key and value
    projections times a factor from 0.5 to 2 over their heads (on equal
    heads the wrong key-value head is as good as the right one, and a norm
    over all heads is the norm over one), an embedding of std 0.5, the other
    matrices of std 0.1 (five times the initial)."""
    rng = np.random.RandomState(seed)
    values = {}
    for name in sorted(shapes):
        shape = shapes[name]
        if name.endswith("router.bias"):
            value = rng.randn(*shape) * 0.2
        elif "norm" in name:
            value = rng.uniform(0.5, 1.5, shape) \
                * (2.0 if name.endswith("q_norm.w") else 1.0)
        elif name.endswith("router.w"):
            value = rng.randn(*shape) * 0.5
        elif name.endswith("conv.conv.w"):
            value = rng.uniform(-0.6, 0.6, shape) * np.array([0.5, 1.0, 2.0])
        elif name.endswith((".attn.q.w", ".attn.k.w", ".attn.v.w")):
            heads = shape[1] // TINY["head_dim"]
            value = (rng.randn(shape[0], heads, TINY["head_dim"]) * 0.1
                     * np.geomspace(0.5, 2.0, heads)[None, :, None]) \
                .reshape(shape)
        elif name == "embed.w":
            value = rng.randn(*shape) * 0.5
        else:
            value = rng.randn(*shape) * 0.1
        values[name] = value.astype(np.float32)
    return values


FETCHES = ["loss", "ce", "logits", "tokens_per_expert"]
BIASES = [f"l{p}.router.bias" for p in range(2, 6)]


# what each planted fault has to move, at least: the logits or a gradient by
# 1% where the true reference is met within 2e-4
FAULT_WRT = ["embed.w", "l1.conv.in.w", "l1.conv.conv.w", "l1.mlp.up.w",
             "l2.attn.q.w", "l2.attn.k.w", "l2.attn.q_norm.w", "l2.router.w",
             "l2.experts.gate.w", "l5.conv.in.w", "final_norm.w"]
CASE = DecoderCase(models.lfm2_moe.build, TINY, ref, REF_KW, FETCHES,
                   state=BIASES, seeded_values=_seeded_values,
                   fault_wrt=FAULT_WRT)


@pytest.fixture(scope="module")
def tiny():
    return CASE.tiny_model()


CONV = ["conv.in.w", "conv.conv.w", "conv.out.w"]
ATTN = ["attn.q.w", "attn.k.w", "attn.v.w", "attn.o.w", "attn.q_norm.w",
        "attn.k_norm.w"]
DENSE = ["mlp.gate.w", "mlp.up.w", "mlp.down.w"]
MOE = ["router.w", "experts.gate.w", "experts.up.w", "experts.down.w"]
OF_KIND = {"conv": CONV, "full_attention": ATTN}
TRAINED = (["embed.w", "final_norm.w"]
           + [f"l{p}.{n}" for p, kind in zip(PUBLISHED, TYPES)
              for n in ["op_norm.w", "ffn_norm.w"] + OF_KIND[kind]
              + (DENSE if p < 2 else MOE)])


def test_tiny_model_has_the_reference_parameters(tiny):
    CASE.has_the_reference_parameters(tiny, TRAINED, {
        "embed.w": (128, 64), "l1.conv.in.w": (64, 3 * 64),     # [B | C | x']
        "l3.conv.conv.w": (64, 3), "l5.conv.out.w": (64, 64),
        "l2.attn.q.w": (64, 4 * 16), "l2.attn.o.w": (64, 4 * 16),
        "l2.attn.k.w": (64, 2 * 16), "l2.attn.v.w": (64, 2 * 16),
        "l2.attn.q_norm.w": (16,), "l2.attn.k_norm.w": (16,),
        "l1.mlp.gate.w": (64, 96), "l2.router.w": (64, 16),
        "l2.router.bias": (16,), "l4.experts.gate.w": (4, 64, 32),
        "l4.experts.up.w": (4, 64, 32), "l5.experts.down.w": (4, 32, 64)})
    # tied; no layer has a shared expert, no conv a bias, layer 1 no router
    assert not any(n == "head.w" or ".shared." in n or n.endswith("conv.b")
                   or n.startswith("l1.router") for n in tiny["params"])


def test_the_initial_values_are_the_assumed_ones():
    main, startup, _, _ = CASE.program()
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    value = lambda n: np.asarray(scope.find_var(n))
    taps = value("l1.conv.conv.w")
    assert np.abs(taps).max() <= 3 ** -0.5 and np.abs(taps).max() > 0.5
    for name in ("l1.conv.in.w", "l1.conv.out.w", "l2.attn.q.w",
                 "l1.mlp.down.w", "l2.experts.up.w", "embed.w"):
        assert 0.018 < value(name).std() < 0.022, name
    assert all(np.all(value(n) == 1) for n in
               ("l1.op_norm.w", "l1.ffn_norm.w", "l2.attn.q_norm.w",
                "l2.attn.k_norm.w", "final_norm.w"))
    assert all(np.all(value(n) == 0) for n in BIASES)


@pytest.mark.parametrize("name", FETCHES)
def test_tiny_model_output_matches_reference(tiny, name):
    CASE.output_matches_reference(tiny, name)


def test_tiny_routing_sends_most_assignments_elsewhere(tiny):
    CASE.routing_sends_most_assignments_elsewhere(tiny, routed_layers=4)


@pytest.mark.parametrize("name", TRAINED)
def test_tiny_model_gradient_matches_reference(tiny, name):
    CASE.gradient_matches_reference(tiny, name)


@pytest.mark.parametrize("layer", [2, 3, 4, 5])
def test_one_step_moves_the_bias_as_next_bias_does(tiny, layer):
    CASE.one_step_moves_the_bias_as_next_bias_does(
        tiny, f"l{layer}.router.bias", GAMMA)


@pytest.mark.parametrize("amp", [False, True])
def test_three_adam_steps_move_the_bias_exactly(amp):
    """`b` after three steps is `next_bias` applied three times to the
    system's own counts, bit for bit; it has no gradient and no moments and
    stays float32 under AMP."""
    main, startup, fetches, _ = CASE.program(
        fluid.optimizer.Adam(learning_rate=1e-3))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace(), amp=amp)
    exe.run(startup, scope=scope)
    want = {n: np.zeros(16, np.float32) for n in BIASES}
    for step in range(3):
        (counts,) = exe.run(main, feed=CASE.batch(step),
                            fetch_list=[fetches["tokens_per_expert"]],
                            scope=scope)
        for i, n in enumerate(BIASES):
            want[n] = np.asarray(ref.next_bias(want[n], counts[i], GAMMA))
    for n in BIASES:
        b = scope.find_var(n)
        assert b.dtype == jnp.float32 and np.array_equal(np.asarray(b),
                                                         want[n])
        assert np.abs(want[n]).max() > 0
    block = main.global_block()
    assert not block.has_var("l2.router.bias@GRAD")
    state = set(scope.local_var_names())
    assert any(n.startswith("l2.router.w_moment") for n in state)
    assert not any(n.startswith("l2.router.bias_") for n in state)
    assert block.var("l5.router.bias").trainable is False
    assert block.var("l5.router.bias").persistable


# -- the tied table ------------------------------------------------------------------------------

def test_the_tied_tables_gradient_is_the_sum_of_its_two_uses(tiny):
    """The same weights with the head as a parameter of its own, `head.w` =
    `embed.w`^T: the logits are the tied model's, and the tied table's
    gradient is the untied embedding's (the look-up's row scatter) plus the
    untied head's, transposed (the dense product)."""
    weights = {**tiny["params"], "head.w": tiny["params"]["embed.w"].T.copy()}
    _, params, _, got, grads, _ = CASE.run_tiny(
        amp=False, weights=weights, tie_embeddings=False)
    assert sorted(params) == sorted(TRAINED + BIASES + ["head.w"])
    assert rel_err(got["logits"], tiny["got"]["logits"]) < 1e-6
    both = grads["embed.w"] + grads["head.w"].T
    assert frob(tiny["grads"]["embed.w"], both) < 1e-6
    # and neither part alone is it
    assert frob(tiny["grads"]["embed.w"], grads["embed.w"]) > 0.1
    assert frob(tiny["grads"]["embed.w"], grads["head.w"].T) > 0.1
    # the reference, untied, agrees with the untied program
    want, want_grads = ref.loss_and_grads(
        params, tiny["tokens"], tiny["labels"], wrt=["embed.w", "head.w"],
        **{**REF_KW, "tie_embeddings": False})
    assert abs(float(want["loss"]) - float(got["loss"][0])) < 1e-5
    for name in ("embed.w", "head.w"):
        assert frob(grads[name], want_grads[name]) < 2e-4, name


def test_the_table_is_read_twice_and_summed_once():
    main, _, _, _ = CASE.program(fluid.optimizer.Adam(learning_rate=1e-3))
    block = main.global_block()
    reads = [op.type for op in block.ops
             if op.attrs.get("__role__") is None
             and "embed.w" in op.input_arg_names]
    assert sorted(reads) == ["lookup_table", "matmul"]
    assert census.parameter_sharing(main)["grad_fanin_max"] == 2
    updates = [op for op in block.ops if op.type == "adam"
               and op.input("Param") == ["embed.w"]]
    assert len(updates) == 1
    assert not any(p.name == "head.w" for p in block.all_parameters())


# -- the planted faults --------------------------------------------------------------------------

@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_each_planted_fault_is_refused(tiny, fault):
    """(`untied_head` moves no forward number: the table's gradient
    alone.)"""
    CASE.planted_fault_is_refused(tiny, fault)


def test_the_config_names_every_fault_and_no_other():
    assert sorted(CONFIG["reference"]["check"]["faults"]) == sorted(ref.FAULTS)
    assert len(ref.FAULTS) == 19


def test_an_unknown_fault_is_refused(tiny):
    CASE.unknown_fault_is_refused(tiny)


def test_reference_in_blocks_is_the_reference(tiny):
    CASE.reference_in_blocks_is_the_reference(
        tiny, ["l1.conv.in.w", "l3.conv.conv.w", "l2.attn.k.w", "l2.router.w",
               "l4.experts.down.w", "embed.w"], q_block=32)


def test_reference_last_positions_equal_the_full_pass(tiny):
    CASE.reference_last_positions_equal_the_full_pass(tiny)


def test_reference_in_bfloat16_is_another_number(tiny):
    CASE.reference_in_bfloat16_is_another_number(tiny)


def test_the_cut_follows_the_published_indices(tiny):
    """`first_layer` 1: the names carry the published index, layer 1 is the
    last dense layer, and the same kinds built from `first_layer` 0 put the
    dense MLP into TWO layers."""
    main, _, _, _ = CASE.program(first_layer=0)
    names = [p.name for p in main.global_block().all_parameters()]
    assert "l0.conv.in.w" in names and "l1.attn.q.w" in names
    assert {"l0.mlp.gate.w", "l1.mlp.gate.w", "l2.router.w"} <= set(names)
    assert "l1.router.w" not in names
    assert list(models.lfm2_moe.LFM2_8B_A1B) == CONFIG["layer_types"] \
        == list(ref.LAYER_TYPES)
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match="layer_types holds"):
            models.lfm2_moe.build(**{**TINY, "layer_types": ["conv", "mamba"]})


# -- AMP -----------------------------------------------------------------------------------------

def test_tiny_model_amp_within_bf16_of_reference():
    """Under AMP the projections, the gates' products, attention, the
    experts and the head's product (the table cast once) are bf16; the
    router's scores, `b`, the convolution's sums, every norm's statistics
    and rotary's trigonometry stay float32. At the initial weights (a
    sharper router flips a few assignments under bf16 inputs)."""
    # a routed expert's gradient feels every assignment that a bf16 input
    # flips to another expert (a whole row of it)
    CASE.amp_within_bf16_of_reference(
        {0.08: ("l1.conv.in.w", "l1.conv.out.w", "l2.attn.q.w",
                "l2.attn.k.w", "l1.mlp.gate.w", "l5.conv.in.w", "embed.w",
                "l1.conv.conv.w"), 0.12: ("l2.experts.gate.w",)}, most=1.0)


def test_amp_lists_leave_the_convolution_and_the_gates_alone():
    assert "moe_router" in registry.AMP_F32_OPS
    assert "matmul" in registry.AMP_BF16_OPS
    assert "elementwise_mul" in registry.AMP_DOWNCAST_OPS
    for op in ("causal_conv1d", "slice", "lookup_table", "rms_norm",
               "rotary_embedding"):
        assert op not in registry.AMP_F32_OPS | registry.AMP_BF16_OPS


def test_five_adam_steps_lower_the_loss():
    CASE.adam_steps_lower_the_loss()


# -- what the Program holds; spans and counters --------------------------------------------------

# its own body: a case a layer, and the ops of a scope counted and in order
@pytest.mark.parametrize("layer", PUBLISHED)
def test_every_layer_is_built_under_its_name_scopes(tiny, layer):
    """A layer's operator with its norm and add under `l<p>.conv` or
    `l<p>.attn`, the conv operator between its projections under
    `l<p>.conv/core` (three slices, two gates, one convolution with no
    activation), its feed-forward with its norm and add under `l<p>.mlp` or
    `l<p>.moe`."""
    scopes = _forward_ops_by_scope(tiny["main"])
    kind = TYPES[layer - 1]
    assert [f"l{layer}.{k}" in scopes for k in ("conv", "attn")] \
        == [kind == "conv", kind == "full_attention"]
    if kind == "conv":
        assert scopes[f"l{layer}.conv"] == ["rms_norm", "mul", "mul",
                                            "elementwise_add"]
        assert scopes[f"l{layer}.conv/core"] == [
            "slice", "slice", "slice", "elementwise_mul", "causal_conv1d",
            "elementwise_mul"]
    else:
        ops = scopes[f"l{layer}.attn"]
        assert ops.count("fused_attention") == 1
        assert ops.count("rotary_embedding") == 2
        assert ops.count("rms_norm") == 3 and ops.count("mul") == 4
        assert f"l{layer}.attn/core" not in scopes
    dense = layer < 2
    assert [f"l{layer}.{k}" in scopes for k in ("mlp", "moe")] \
        == [dense, not dense]
    fed = scopes[f"l{layer}." + ("mlp" if dense else "moe")]
    assert fed.count("swiglu") == 1 and fed.count("rms_norm") == 1
    assert fed.count("moe_router") == (not dense)
    assert fed[-1] == "elementwise_add"
    # outside every scope: the look-up, the final norm, the tied head, the loss
    assert scopes[None][:1] == ["lookup_table"]
    assert scopes[None][1:4] == ["rms_norm", "matmul",
                                 "softmax_with_cross_entropy"]


def test_the_convolutions_have_no_activation_and_no_bias(tiny):
    convs = [o for o in tiny["main"].global_block().ops
             if o.type == "causal_conv1d"]
    assert len(convs) == 4
    assert all(o.attrs["activation"] == "" and not o.inputs.get("Bias")
               for o in convs)


CENSUS = {"layer_kinds": {"full_attention": 1, "short_conv": 4},
          "short_conv_layers": 4, "short_conv_taps": 3, "short_conv_gates": 8,
          "attention_kv_group": 2, "attention_rotary_layers": 1,
          "dense_ffn_layers": 1, "moe_router_score": "sigmoid",
          "moe_router_bias_updates": 4, "moe_experts_routed": 16,
          "moe_experts_held": 4, "tied_heads": 1}


def test_layer_census_reads_the_issues_counts():
    main, _, _, _ = CASE.program(fluid.optimizer.SGD(learning_rate=1e-3))
    got = census.layer_census(main)
    assert got == CENSUS
    for absent in ("attention_unrotated_layers", "residual_out_norms",
                   "moe_router_groups", "state_space_layers"):
        assert absent not in got
    # at the published heads the group is 4
    main, _, _, _ = CASE.program(n_head=32, n_kv_head=8, head_dim=4)
    assert census.layer_census(main)["attention_kv_group"] == 4
    main, _, _, _ = CASE.program(tie_embeddings=False)
    assert "tied_heads" not in census.layer_census(main)


@pytest.fixture(scope="module")
def compile_detail():
    return CASE.compile_detail()


@pytest.mark.parametrize("key,value", sorted(
    {**CENSUS, "causal_conv_plan": "xla", "grad_fanin_max": 2,
     "moe_row_buffer_rows": 2 * 128 * 4 + 4 * 128}.items()))
def test_compile_event_carries_the_census(compile_detail, key, value):
    carries_the_census(compile_detail, {key: value})


@pytest.mark.parametrize("model,has_conv", [
    ("qwen3_next", True), ("olmo_hybrid", True), ("nemotron_h", True),
    ("granite_hybrid", True), ("trinity", False), ("olmoe", False)])
def test_the_new_keys_go_with_what_they_count(model, has_conv):
    """A convolution with silu in front of a scan or a delta rule is no
    short-conv layer: no accepted program gains a `short_conv` key, and only
    a program with a convolution notes its plan."""
    main, startup, feeds, fetches = build_program(model)
    got = census.layer_census(main)
    assert not [k for k in got if k.startswith("short_conv")]
    assert "short_conv" not in got.get("layer_kinds", {})
    convs = [o for o in main.global_block().ops if o.type == "causal_conv1d"]
    assert bool(convs) == has_conv
    assert all(o.attrs["activation"] == "silu" for o in convs)


def test_a_bare_convolution_in_front_of_a_scan_is_no_short_conv():
    """The kind goes with the layer, not with the attribute: the same op
    under a scope that holds a delta rule counts nothing."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = layers.data(name="x", shape=[1, 64, 4, 8], dtype="float32",
                        append_batch_size=False)
        ab = layers.data(name="ab", shape=[1, 64, 4], dtype="float32",
                         append_batch_size=False)
        with fluid.name_scope("l0.gdn"):
            flat = layers.reshape(x, shape=[0, 0, 32])
            conv = layers.causal_conv1d(flat, 3, activation=None)
            q = layers.reshape(conv, shape=[0, 0, 4, 8])
            layers.gated_delta_rule(q, q, q, a=ab, b=ab)
    got = census.layer_census(main)
    assert got["layer_kinds"] == {"linear_attention": 1}
    assert not [k for k in got if k.startswith("short_conv")]


# -- the copies and the harness ------------------------------------------------------------------

def test_the_two_copies_of_the_reference_are_identical():
    CASE.two_copies_of_the_reference_are_identical()


def test_the_config_holds_the_published_widths_and_the_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = []
    if os.path.exists(catalog):     # the builder's machine has it
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
    want = {"hidden_size": 2048, "intermediate_size": 7168,
            "moe_intermediate_size": 1792, "num_attention_heads": 32,
            "num_key_value_heads": 8, "conv_L_cache": 3, "conv_bias": False,
            "num_experts_per_tok": 4, "num_dense_layers": 2,
            "norm_topk_prob": True, "use_expert_bias": True,
            "routed_scaling_factor": 1, "rope_theta": 1000000,
            "norm_eps": 1e-05, "max_position_embeddings": 128000,
            "model_type": "lfm2_moe"}
    for key, value in want.items():
        assert CONFIG[key] == value, key
    for row in rows:
        if row["name"] == "LFM2-8B-A1B":
            assert CONFIG["source"] == row["source_url"]
            cut = {"num_hidden_layers": 5, "num_experts": 8,
                   "vocab_size": 16384}
            for key, value in row["config"].items():
                assert CONFIG[key] == cut.get(key, value), key
            for key in cut:
                assert CONFIG[key + "_published"] == row["config"][key], key
    assert [line.split()[0] for line in CONFIG["reduced"]] \
        == ["num_hidden_layers", "num_experts", "vocab_size"]
    args = CONFIG["build_args"]
    assert (args["d_model"], args["d_dense"], args["d_expert"],
            args["n_head"], args["n_kv_head"], args["head_dim"],
            args["conv_taps"], args["n_expert"], args["top_k"],
            args["rope_theta"], args["rms_eps"], args["route_norm_eps"]) \
        == (2048, 7168, 1792, 32, 8, 64, 3, 32, 4, 1e6, 1e-5, 1e-6)
    assert (args["vocab_size"], args["experts_held"], args["first_expert"],
            args["first_layer"], args["layer_types"]) \
        == (16384, 8, 0, 1, CONFIG["layer_types"][1:6])
    for key in ("the tied table", "the bias rule", "losses",
                "initialisation", "the order of W_in's columns", "optimizer",
                "labels"):
        assert key in CONFIG["assumed"], key


def test_the_config_states_what_build_gives():
    """`parameters` is the count of the published-width program (shapes
    alone: nothing is allocated)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        models.lfm2_moe.build(seq_len=4096, **CONFIG["build_args"])
    shapes = {p.name: tuple(p.shape)
              for p in main.global_block().all_parameters()}
    count = sum(int(np.prod(s)) for s in shapes.values())
    assert count == CONFIG["parameters"] == 507820288
    assert CONFIG["parameter_bytes"]["that_stay"] == 12 * count
    assert CONFIG["parameter_bytes"]["inside_a_step"] == 16 * count
    assert shapes["l1.conv.in.w"] == (2048, 6144)
    assert shapes["l2.experts.gate.w"] == (8, 2048, 1792)
    assert shapes["l2.router.w"] == (2048, 32)
    assert shapes["embed.w"] == (16384, 2048)
    # the issue's arithmetic, and the four biases of 32 it leaves out
    assert count == 60827648 + 98635904 + 314800128 + 33554432 + 2048 \
        + 4 * 32


def test_the_tiny_block_runs_through_the_benchmark():
    runs_through_the_benchmark("lfm2_8b_a1b.s4096")
