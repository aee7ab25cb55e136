"""Trinity-Mini (window layers that turn by rotary beside full layers with no
positions, grouped heads, QK-norm, an output gate from a projection of its
own, four norms a layer, a scaled embedding, a leading dense layer, one chip's
share of a sigmoid-routed expert layer whose selection bias the step rewrites,
a shared expert) through `layers` -> Program IR -> `Executor`, against the
plain reference (`tests/trinity_reference.py`: a masked softmax whose mask is
two inequalities, `jnp.repeat`, a loop over the held experts, `next_bias`).
The sizes are the configuration's `tiny` block. Seeded random weights,
float32, AMP off unless a test says otherwise."""

import filecmp
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models, observe
from paddle_tpu.core import ir, registry

import trinity_reference as ref
from test_kanana2 import _planted
from test_olmoe import rel_err, run_piece
from test_qwen3_next import frob

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
with open(os.path.join(ROOT, "benchmark", "configs",
                       "trinity_mini_26b_a3b.json")) as f:
    CONFIG = json.load(f)
GAMMA = 0.001
# 5 layers (one dense, then sliding x 3 and full, all but the first over
# experts), hidden 64, 4/2 heads of 32, window 96 over 256 tokens, 16 experts
# top-4, 4 held from expert 4
TINY = {**{k: CONFIG["build_args"][k] for k in (
    "layer_types", "rope_theta", "n_shared", "route_scale",
    "bias_update_rate", "rms_eps")}, **CONFIG["tiny"]["build_args"]}
KINDS = TINY["layer_types"]
REF_KW = {k: TINY[k] for k in (
    "n_layer", "n_head", "n_kv_head", "head_dim", "layer_types",
    "sliding_window", "rope_theta", "top_k", "first_expert", "route_scale",
    "rms_eps")}
RTOL = 2e-5


def test_the_tiny_block_is_the_issues():
    assert KINDS == ["sliding_attention"] * 4 + ["full_attention"]
    assert (TINY["n_layer"], TINY["n_dense_layer"], TINY["d_model"]) == \
        (5, 1, 64)
    assert (TINY["n_head"], TINY["n_kv_head"], TINY["head_dim"]) == (4, 2, 32)
    assert (TINY["sliding_window"], TINY["seq_len"]) == (96, 256)
    assert (TINY["n_expert"], TINY["top_k"], TINY["experts_held"],
            TINY["first_expert"]) == (16, 4, 4, 4)
    assert TINY["bias_update_rate"] == GAMMA


# -- the shares add up -----------------------------------------------------------------

@pytest.mark.parametrize("n_expert,held,k,width", [(16, 4, 4, 12),
                                                   (128, 8, 8, 8)],
                         ids=["four_shares_of_4", "sixteen_shares_of_8"])
def test_the_shares_add_up_to_the_whole_layer(n_expert, held, k, width):
    """The routed parts that all the shares give, plus the shared expert
    once, are the uncut reference's whole layer: forward, the gradient of the
    router and of the layer's input. With a planted non-zero `b`, so that
    choosing by `s + b` and weighting by `s` cannot be confused."""
    d = 16
    rng = np.random.RandomState(5)
    x = rng.randn(40, d).astype(np.float32)
    whole = {"router.w": rng.randn(d, n_expert),
             "router.bias": rng.randn(n_expert) * 0.3,
             "experts.gate.w": rng.randn(n_expert, d, width) * 0.3,
             "experts.up.w": rng.randn(n_expert, d, width) * 0.3,
             "experts.down.w": rng.randn(n_expert, width, d) * 0.3,
             "shared.gate.w": rng.randn(d, width) * 0.3,
             "shared.up.w": rng.randn(d, width) * 0.3,
             "shared.down.w": rng.randn(width, d) * 0.3}
    whole = {n: v.astype(np.float32) for n, v in whole.items()}
    shares = n_expert // held
    cut = {f"s{j}.{which}.w":
           whole[f"experts.{which}.w"][j * held:(j + 1) * held]
           for j in range(shares) for which in ("gate", "up", "down")}

    def build(data):
        routing = layers.moe_router(
            data["x"], n_expert, k, norm_topk_prob=True,
            score_func="sigmoid", norm_eps=1e-20, scaling_factor=2.826,
            param_attr=fluid.ParamAttr(name="router.w"),
            bias_attr=_planted("router.bias", whole["router.bias"]))
        parts = [layers.moe_experts(
            data["x"], routing, n_expert, width, name=f"s{j}",
            first_expert=j * held, experts_held=held)
            for j in range(shares)]

        def fc(v, size, name):
            return layers.fc(v, size, bias_attr=False,
                             param_attr=fluid.ParamAttr(name=name))

        hidden = layers.swiglu(fc(data["x"], width, "shared.gate.w"),
                               fc(data["x"], width, "shared.up.w"))
        return [layers.sums(parts + [fc(hidden, d, "shared.down.w")])] + parts

    params = {**{n: v for n, v in whole.items()
                 if not n.startswith(("experts.", "router.bias"))}, **cut}
    outs, grads, probe = run_piece(build, {"x": x}, params)
    kw = dict(top_k=k, route_scale=2.826)

    def want(x, router_w):
        return ref.sparse_experts({**whole, "router.w": router_w}, x,
                                  first_expert=0, **kw)[0]

    with jax.default_matmul_precision("highest"):
        assert rel_err(outs[0], want(x, whole["router.w"])) < RTOL
        gx, gr = jax.grad(lambda a, b: jnp.sum(want(a, b) * probe),
                          (0, 1))(x, whole["router.w"])
        none = {n: v[:0] for n, v in whole.items() if n.startswith("experts.")}
        shared = ref.sparse_experts({**whole, **none}, x, first_expert=0,
                                    **kw)[0]
        # and a share alone is the reference given that share
        for j in (0, shares - 1):
            own = {n: (v[j * held:(j + 1) * held]
                       if n.startswith("experts.") else v)
                   for n, v in whole.items()}
            alone = ref.sparse_experts(own, x, first_expert=j * held, **kw)[0]
            assert rel_err(outs[1 + j], alone - shared) < 1e-4, j
        # the bias mattered: at b = 0 the layer is another function
        unbiased = ref.sparse_experts(
            {**whole, "router.bias": np.zeros(n_expert, np.float32)}, x,
            first_expert=0, **kw)[0]
        assert rel_err(unbiased, want(x, whole["router.w"])) > 0.05
    assert rel_err(grads["x"], gx) < 1e-4
    assert rel_err(grads["router.w"], gr) < 1e-4


# -- the model ----------------------------------------------------------------------------

def _program(optimizer=None, **sizes):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, fetches = models.trinity.build(**{**TINY, **sizes})
        if optimizer is None:
            pairs = fluid.append_backward(fetches["loss"])
        else:
            optimizer.minimize(fetches["loss"])
            pairs = []
    main.random_seed = startup.random_seed = 7
    return main, startup, fetches, pairs


def _batch(seed=0, batch=2):
    rng = np.random.RandomState(seed)
    shape = (batch, TINY["seq_len"])
    return {"tokens": rng.randint(0, TINY["vocab_size"], shape)
            .astype(np.int32),
            "labels": rng.randint(0, TINY["vocab_size"], shape)
            .astype(np.int32)}


def _parameter_names(main):
    return [p.name for p in main.global_block().all_parameters()]


def _seeded_weights(scope, names, seed=3):
    """Weights far from their initial values, so that no term of the
    comparison is small by construction: norm weights in [0.5, 1.5], a router
    five times as sharp, a planted bias of std 0.2 (the sigmoids' spread is
    about 0.25), an embedding of std 0.02 (scaled by 8 in the model), the
    other matrices of std 0.1 (five times the initial)."""
    rng = np.random.RandomState(seed)
    for name in sorted(names):
        shape = np.shape(scope.find_var(name))
        if name.endswith("router.bias"):
            value = rng.randn(*shape) * 0.2
        elif "norm" in name:
            value = rng.uniform(0.5, 1.5, shape)
        elif name.endswith("router.w"):
            value = rng.randn(*shape) * 0.5
        elif name == "embed.w":
            value = rng.randn(*shape) * 0.02
        else:
            value = rng.randn(*shape) * 0.1
        scope.set_var(name, jnp.asarray(value.astype(np.float32)))


FETCHES = ["loss", "ce", "logits", "tokens_per_expert"]


def _run_tiny(amp, seeded=True):
    main, startup, fetches, pairs = _program()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace(), amp=amp)
    exe.run(startup, scope=scope)
    names = _parameter_names(main)
    if seeded:
        _seeded_weights(scope, names)
    params = {n: np.asarray(scope.find_var(n)) for n in names}
    feed = _batch()
    out = exe.run(main, feed=feed,
                  fetch_list=[fetches[n] for n in FETCHES]
                  + [g for _, g in pairs], scope=scope)
    got = dict(zip(FETCHES, out))
    grads = dict(zip((p.name for p, _ in pairs), out[len(FETCHES):]))
    after = {n: np.asarray(scope.find_var(n)) for n in names
             if n.endswith("router.bias")}
    return main, params, feed, got, grads, after


@pytest.fixture(scope="module")
def tiny():
    main, params, feed, got, grads, after = _run_tiny(amp=False)
    tokens, labels = jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"])
    want, want_grads = ref.loss_and_grads(
        params, tokens, labels, last=TINY["seq_len"], **REF_KW)
    return dict(main=main, params=params, tokens=tokens, labels=labels,
                got=got, grads=grads, after=after, want=want,
                want_grads=want_grads)


MIXER = ["in_norm.w", "post_attn_norm.w", "pre_mlp_norm.w", "post_mlp_norm.w",
         "attn.q.w", "attn.k.w", "attn.v.w", "attn.gate.w", "attn.q_norm.w",
         "attn.k_norm.w", "attn.o.w"]
DENSE = ["mlp.gate.w", "mlp.up.w", "mlp.down.w"]
MOE = ["router.w", "experts.gate.w", "experts.up.w", "experts.down.w",
       "shared.gate.w", "shared.up.w", "shared.down.w"]
TRAINED = (["embed.w", "final_norm.w", "head.w"]
           + [f"l{i}.{n}" for i in range(5)
              for n in MIXER + (DENSE if i == 0 else MOE)])
BIASES = [f"l{i}.router.bias" for i in range(1, 5)]


def test_tiny_model_has_the_reference_parameters(tiny):
    assert sorted(tiny["params"]) == sorted(TRAINED + BIASES)
    shapes = {n: v.shape for n, v in tiny["params"].items()}
    assert shapes["l0.attn.q.w"] == shapes["l0.attn.gate.w"] == (64, 4 * 32)
    assert shapes["l4.attn.k.w"] == shapes["l4.attn.v.w"] == (64, 2 * 32)
    assert shapes["l0.attn.o.w"] == (4 * 32, 64)
    assert shapes["l0.attn.q_norm.w"] == shapes["l4.attn.k_norm.w"] == (32,)
    assert shapes["l1.experts.gate.w"] == (4, 64, 32)
    assert shapes["l1.router.w"] == (64, 16)
    assert shapes["l1.router.bias"] == (16,)
    assert shapes["l1.shared.gate.w"] == (64, 32)
    assert shapes["l0.mlp.gate.w"] == (64, 96)
    # a gradient for every trained parameter and for no bias
    assert sorted(tiny["grads"]) == sorted(TRAINED)


@pytest.mark.parametrize("name", FETCHES)
def test_tiny_model_output_matches_reference(tiny, name):
    if name == "tokens_per_expert":
        assert np.array_equal(tiny["got"][name], tiny["want"][name])
    else:
        want = np.asarray(tiny["want"][name])
        assert rel_err(np.reshape(tiny["got"][name], want.shape), want) < 1e-4


def test_tiny_routing_sends_most_assignments_elsewhere(tiny):
    counts = tiny["got"]["tokens_per_expert"]
    assert counts.shape == (4, 16) and np.all(counts.sum(1) == 2 * 256 * 4)
    held = counts[:, 4:8].sum(1)
    assert np.all(held > 0) and np.all(held < counts.sum(1) / 2)


@pytest.mark.parametrize("name", TRAINED)
def test_tiny_model_gradient_matches_reference(tiny, name):
    assert frob(tiny["grads"][name], tiny["want_grads"][name]) < 2e-4


@pytest.mark.parametrize("layer", [1, 2, 3, 4])
def test_one_step_moves_the_bias_as_next_bias_does(tiny, layer):
    name = f"l{layer}.router.bias"
    want = ref.next_bias(tiny["params"][name],
                         tiny["got"]["tokens_per_expert"][layer - 1], GAMMA)
    assert np.array_equal(tiny["after"][name], np.asarray(want))
    moved = tiny["after"][name] - tiny["params"][name]
    assert np.all(np.isclose(np.abs(moved), GAMMA, rtol=1e-3)
                  | (moved == 0)) and np.any(moved != 0)


# what each planted fault has to move, at least: the logits or a gradient by
# 1% where the true reference is met within 2e-4
FAULT_WRT = ["l1.attn.q.w", "l1.attn.k.w", "l1.attn.gate.w", "l4.attn.q.w",
             "l4.attn.k.w", "l4.attn.gate.w", "l2.post_attn_norm.w",
             "l1.router.w", "embed.w"]


@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_each_planted_fault_is_refused(tiny, fault):
    """The comparison that passes the reference refuses each fault: the
    logits, the loss or a gradient moves by far more than the system's
    distance from the true reference."""
    bad, bad_grads = ref.loss_and_grads(
        tiny["params"], tiny["tokens"], tiny["labels"], wrt=FAULT_WRT,
        last=TINY["seq_len"], fault=fault, **REF_KW)
    moved = [rel_err(tiny["got"]["logits"], bad["logits"])] \
        + [frob(tiny["grads"][n], bad_grads[n]) for n in FAULT_WRT]
    held = [rel_err(tiny["got"]["logits"], tiny["want"]["logits"])] \
        + [frob(tiny["grads"][n], tiny["want_grads"][n]) for n in FAULT_WRT]
    assert max(held) < 2e-4
    assert max(moved) > 50 * 2e-4, (fault, moved)
    assert abs(float(bad["loss"]) - float(tiny["want"]["loss"])) > 1e-5


def test_the_config_names_every_fault_and_no_other():
    assert sorted(CONFIG["reference"]["check"]["faults"]) == sorted(ref.FAULTS)
    assert len(ref.FAULTS) == 10


def test_an_unknown_fault_is_refused(tiny):
    with pytest.raises(ValueError, match="fault is one of"):
        ref.loss_parts(tiny["params"], tiny["tokens"], tiny["labels"],
                       fault="no_such", **REF_KW)


def test_interpreted_kernels_give_the_reference_too(monkeypatch):
    """The same program with the flash kernels under the Pallas interpreter
    (the windowed one-pass forward and the fused backward at 256 tokens)
    instead of the CPU path's jnp reference."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    _, params, feed, got, grads, _ = _run_tiny(amp=False)
    want, want_grads = ref.loss_and_grads(
        params, jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"]),
        wrt=["l0.attn.q.w", "l1.attn.k.w", "l2.attn.v.w", "l4.attn.q.w",
             "l4.attn.gate.w"],
        last=TINY["seq_len"], **REF_KW)
    assert rel_err(got["logits"], want["logits"]) < 1e-4
    for name, g in want_grads.items():
        assert frob(grads[name], g) < 2e-4, name


def test_reference_in_blocks_is_the_reference(tiny):
    """`q_block` and `remat` are the reference's memory, not its
    mathematics."""
    parts, grads = ref.loss_and_grads(
        tiny["params"], tiny["tokens"], tiny["labels"],
        wrt=["l0.attn.q.w", "l1.attn.gate.w", "l4.attn.k.w", "l2.router.w",
             "embed.w"],
        q_block=32, remat=True, **REF_KW)
    assert abs(float(parts["loss"]) - float(tiny["want"]["loss"])) < 1e-5
    for name, g in grads.items():
        assert frob(g, tiny["want_grads"][name]) < 1e-5, name


def test_reference_last_positions_equal_the_full_pass(tiny):
    parts = ref.loss_parts(tiny["params"], tiny["tokens"], tiny["labels"],
                           last=16, **REF_KW)
    assert rel_err(parts["logits"], tiny["want"]["logits"][:, -16:]) < 1e-6


def test_reference_in_bfloat16_is_another_number(tiny):
    low = ref.loss_parts(tiny["params"], tiny["tokens"], tiny["labels"],
                         dtype=jnp.bfloat16, **REF_KW)
    assert low["loss"].dtype == jnp.bfloat16
    assert abs(float(low["loss"]) - float(tiny["want"]["loss"])) > 1e-4


# -- the bias as state -------------------------------------------------------------------

@pytest.mark.parametrize("amp", [False, True])
def test_three_adam_steps_move_the_bias_exactly(amp):
    """`b` after three steps is `next_bias` applied three times to the
    system's own counts, bit for bit; it has no gradient and no moments and
    stays float32 under AMP."""
    main, startup, fetches, _ = _program(
        fluid.optimizer.Adam(learning_rate=1e-3))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace(), amp=amp)
    exe.run(startup, scope=scope)
    assert np.all(np.asarray(scope.find_var("l1.router.bias")) == 0)
    want = {n: np.zeros(16, np.float32) for n in BIASES}
    for step in range(3):
        (counts,) = exe.run(main, feed=_batch(step),
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
    assert not block.has_var("l1.router.bias@GRAD")
    state = set(scope.local_var_names())
    assert any(n.startswith("l1.router.w_moment") for n in state)
    assert not any(n.startswith("l1.router.bias_") for n in state)
    assert block.var("l4.router.bias").trainable is False
    assert block.var("l4.router.bias").persistable


# -- what the Program holds --------------------------------------------------------------------

def _forward_ops_by_scope(main):
    scopes = {}
    for op in main.global_block().ops:
        if op.attrs.get("__role__") is None:
            scopes.setdefault(op.attrs.get(ir.NAME_SCOPE_ATTR), []) \
                .append(op.type)
    return scopes


@pytest.mark.parametrize("layer", range(5))
def test_only_the_sliding_mixers_turn(tiny, layer):
    """The full layer's mixer holds no `rotary_embedding` op and each sliding
    one holds two (q and k); every mixer holds one gate (a sigmoid and a
    product) and four norms: in, q, k, out."""
    scopes = _forward_ops_by_scope(tiny["main"])
    sliding = KINDS[layer] == "sliding_attention"
    ops = scopes[f"l{layer}." + ("swa" if sliding else "attn")]
    assert f"l{layer}." + ("attn" if sliding else "swa") not in scopes
    assert ops.count("rotary_embedding") == (2 if sliding else 0)
    assert ops.count("sigmoid") == 1 and ops.count("elementwise_mul") == 1
    assert ops.count("rms_norm") == 4 and ops.count("fused_attention") == 1
    (attention,) = [o for o in tiny["main"].global_block().ops
                    if o.type == "fused_attention" and o.attrs.get(
                        ir.NAME_SCOPE_ATTR, "").startswith(f"l{layer}.")]
    assert attention.attrs.get("window") == (96 if sliding else None)
    fed = scopes[f"l{layer}." + ("mlp" if layer == 0 else "moe")]
    assert fed.count("rms_norm") == 2
    assert ("moe_router" in fed) == (layer > 0)
    assert fed.count("swiglu") == (1 if layer == 0 else 2)


def test_every_layer_is_built_under_its_name_scopes(tiny):
    scopes = {k: set(v) for k, v in
              _forward_ops_by_scope(tiny["main"]).items()}
    assert {"l0.swa", "l3.swa", "l4.attn", "l0.mlp", "l1.moe",
            "l4.moe"} <= set(scopes)
    assert "l0.moe" not in scopes and "l1.mlp" not in scopes
    assert {"fused_attention", "rotary_embedding", "expand", "rms_norm",
            "sigmoid", "elementwise_mul"} <= scopes["l1.swa"]
    assert {"moe_router", "moe_dispatch", "grouped_matmul", "moe_combine",
            "sign", "assign", "rms_norm"} <= scopes["l2.moe"]
    # the embedding's scale is the one op between the look-up and layer 0
    first = [o.type for o in tiny["main"].global_block().ops[:3]]
    assert first[:2] == ["lookup_table", "scale"]
    scale = tiny["main"].global_block().ops[1]
    assert scale.attrs["scale"] == 8.0


def test_attention_ops_have_the_groups_shapes():
    main, _, _, _ = _program()
    block = main.global_block()
    attention = [o for o in block.ops if o.type == "fused_attention"]
    assert len(attention) == 5
    for op in attention:
        for slot in ("Q", "K", "V"):
            assert block.var(op.input(slot)[0]).shape[1:] == (4, 256, 32)
        assert op.attrs["sm_scale"] == 32 ** -0.5
    expands = [o for o in block.ops if o.type == "expand"]
    assert [o.attrs["expand_times"] for o in expands] == [[1, 1, 2, 1, 1]] * 10


def test_tiny_model_amp_within_bf16_of_reference():
    """Under AMP the residual stream, the projections, attention, the gate
    and the experts are bf16; the router's scores, `b`, every norm's
    statistics and rotary's trigonometry stay float32. At the initial
    weights (a sharper router flips a few assignments under bf16 inputs)."""
    main, params, feed, got, grads, after = _run_tiny(amp=True, seeded=False)
    want, want_grads = ref.loss_and_grads(
        params, jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"]),
        last=TINY["seq_len"], **REF_KW)
    assert abs(float(got["loss"][0]) - float(want["loss"])) < 0.002
    assert got["logits"].dtype == jnp.bfloat16
    err = np.abs(np.asarray(got["logits"], np.float32)
                 - np.asarray(want["logits"]))
    std = float(np.std(want["logits"]))
    # the largest is a token whose assignment flipped: its experts' output
    # is normed to unit size on the way out, whatever its own size was
    assert err.mean() < 0.02 * std and err.max() < std
    for name in ("l0.attn.q.w", "l1.attn.k.w", "l1.attn.gate.w",
                 "l4.attn.q.w", "l4.attn.gate.w", "l0.mlp.gate.w",
                 "l1.experts.gate.w", "l1.shared.up.w", "embed.w"):
        assert grads[name].dtype == np.float32
        # a routed expert's gradient feels every assignment that a bf16
        # input flips to another expert (a whole row of it)
        limit = 0.12 if ".experts." in name else 0.08
        assert frob(grads[name], want_grads[name]) < limit, name
    for name in BIASES:
        assert after[name].dtype == np.float32


def test_amp_lists_hold_the_router_and_leave_the_gate_alone():
    assert "moe_router" in registry.AMP_F32_OPS
    assert "reduce_mean" in registry.AMP_F32_OPS
    assert "fused_attention" in registry.AMP_BF16_OPS
    assert "elementwise_mul" in registry.AMP_DOWNCAST_OPS
    # the gate's sigmoid runs in the dtype that reaches it (the projection's
    # bf16); the bias update's ops on float32 values stay float32
    for op in ("sigmoid", "assign", "sign", "scale", "cast", "sum",
               "rotary_embedding", "rms_norm"):
        assert op not in registry.AMP_F32_OPS | registry.AMP_BF16_OPS


def test_five_adam_steps_lower_the_loss():
    main, startup, fetches, _ = _program(
        fluid.optimizer.Adam(learning_rate=3e-3))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = _batch()
    losses = [float(exe.run(main, feed=feed, fetch_list=[fetches["loss"]],
                            scope=scope)[0][0]) for _ in range(6)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05


# -- spans and counters ---------------------------------------------------------------------

CENSUS = {"layer_kinds": {"window_attention": 4, "full_attention": 1},
          "attention_window_layers": 4, "attention_window": 96,
          "attention_kv_group": 2, "attention_rotary_layers": 4,
          "attention_unrotated_layers": 1, "attention_gated_layers": 5,
          "residual_out_norms": 10, "dense_ffn_layers": 1,
          "moe_router_score": "sigmoid", "moe_router_bias_updates": 4,
          "moe_experts_routed": 16, "moe_experts_held": 4,
          "moe_row_buffer_rows": 2 * 256 * 4 + 4 * 128,
          "moe_share_bounded_moves": 4 * 4, "moe_share_bounded_ops": 3 * 4,
          # batch 2 x 4 heads x 4 layers, 128 x 128 tiles under a window of
          # 96: all three of the triangle's meet the band
          "window_tiles_computed": 2 * 4 * 4 * 3,
          # none of them lies wholly under the diagonal and inside the window
          "flash_tiles_unmasked": 0}


@pytest.fixture(scope="module")
def census():
    main, startup, fetches, _ = _program(
        fluid.optimizer.SGD(learning_rate=1e-3))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed=_batch(), fetch_list=[fetches["loss"]], scope=scope)
    latest = observe.observatory().latest
    return latest(main._uid).detail, latest(startup._uid).detail


@pytest.mark.parametrize("key", sorted(CENSUS))
def test_compile_event_carries_the_census(census, key):
    detail, startup_detail = census
    assert detail[key] == CENSUS[key]
    assert key not in startup_detail


def test_the_unmasked_tally_follows_the_tiles(monkeypatch):
    """At tiles of 128 over 512 tokens and a window of 300 a windowed layer
    runs the three tiles next to the diagonal without the causal mask:
    `flash_tiles_unmasked` sums the windowed ops, forward ops only; the full
    layer's six under the diagonal keep the mask and add nothing."""
    from paddle_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (128, 128))
    monkeypatch.setitem(TINY, "seq_len", 512)
    main, startup, fetches, _ = _program(
        fluid.optimizer.SGD(learning_rate=1e-3), sliding_window=300)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    exe.run(main, feed=_batch(), fetch_list=[fetches["loss"]], scope=scope)
    detail = observe.observatory().latest(main._uid).detail
    assert detail["layer_kinds"] == CENSUS["layer_kinds"]
    assert pallas_attention.interior_tiles(512, 300) == 3
    assert detail["window_tiles_computed"] == 2 * 4 * 4 * 10
    assert detail["flash_tiles_unmasked"] == 2 * 4 * 4 * 3


@pytest.mark.parametrize("model,want", [
    ("mellum2", {"attention_rotary_layers": 4}),
    ("kanana2", {"attention_rotary_layers": 3}),
    ("qwen3_next", {"attention_rotary_layers": 1,
                    "attention_gated_layers": 1})])
def test_the_new_census_keys_on_the_other_models(model, want):
    """A program all of whose attention layers turn has no
    `attention_unrotated_layers`; only Qwen3-Next's attention is gated; no
    other model norms a sublayer on the way out but Ouro."""
    import test_kanana2
    import test_mellum2
    import test_qwen3_next
    sizes = {"mellum2": test_mellum2.TINY, "kanana2": test_kanana2.TINY,
             "qwen3_next": test_qwen3_next.TINY}[model]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        getattr(models, model).build(**sizes)
    from paddle_tpu.observe import census
    got = census.layer_census(main)
    new = ("attention_rotary_layers", "attention_unrotated_layers",
           "attention_gated_layers", "residual_out_norms")
    assert {k: got[k] for k in new if k in got} == want


# -- the copies and the harness -----------------------------------------------------------------

def test_the_two_copies_of_the_reference_are_identical():
    assert filecmp.cmp(
        os.path.join(HERE, "trinity_reference.py"),
        os.path.join(ROOT, "benchmark", "references",
                     "trinity_reference.py"), shallow=False)


def test_the_tiny_block_runs_through_the_benchmark():
    """`run.py --tiny` on the cell: the configuration's tiny block through
    the harness's own rehearsal, the in-run reference comparison
    included."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "trinity_mini_26b_a3b.s4096", "--seed", "3000000019",
         "--seconds", "1", "--trace", "0", "--tiny"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "REHEARSAL" in out.stdout and "reference check after" in out.stdout
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
