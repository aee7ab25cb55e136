"""Kanana-2 (latent attention through `fused_attention` with value heads of
their own width, a leading dense layer, one chip's share of a sigmoid-routed
expert layer whose selection bias the step rewrites, two shared experts)
through `layers` -> Program IR -> `Executor`, against the plain reference
(`tests/kanana2_reference.py`: a masked softmax over the assembled heads, a
loop over the held experts, `next_bias`). Seeded random weights, float32, AMP
off unless a test says otherwise."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import io, layers, models
from paddle_tpu.core import ir, registry

import kanana2_reference as ref
from decoder_case import (DIGESTS, DecoderCase, _planted, build_program,
                          carries_the_census, frob,
                          layers_are_built_under_their_scopes, program_digest,
                          rel_err, run_piece, runs_through_the_benchmark,
                          tiny_args)

TINY = tiny_args("kanana2")
GAMMA = TINY["bias_update_rate"]
REF_KW = {k: TINY[k] for k in (
    "n_layer", "n_head", "qk_nope_dim", "qk_rope_dim", "v_head_dim",
    "rope_theta", "top_k", "first_expert", "routed_scaling_factor")}
RTOL = 2e-5


# -- rotary in interleaved pairs ------------------------------------------------

def test_interleaved_rotary_is_the_public_codes_deinterleave_form():
    """`rotary_embedding(interleaved=True)` against `rotary_interleaved`
    (view as pairs, transpose, rotate halves), forward and gradient; and
    against the definition: the pair (x[2i], x[2i+1]) turned by
    t * theta^(-2i/r), laid [evens | odds]."""
    rng = np.random.RandomState(0)
    x = rng.randn(2, 3, 16, 8).astype(np.float32)
    (y,), grads, probe = run_piece(
        lambda d: [layers.rotary_embedding(d["x"], theta=100.0,
                                           interleaved=True)], {"x": x})
    want = ref.rotary_interleaved(jnp.asarray(x), 100.0)
    assert rel_err(y, want) < RTOL
    gx = jax.grad(lambda a: jnp.sum(ref.rotary_interleaved(a, 100.0)
                                    * probe))(jnp.asarray(x))
    assert rel_err(grads["x"], gx) < RTOL
    t = np.arange(16)[:, None]
    angle = t * 100.0 ** (-np.arange(0, 8, 2) / 8)
    even, odd = x[..., 0::2], x[..., 1::2]
    by_hand = np.concatenate([even * np.cos(angle) - odd * np.sin(angle),
                              odd * np.cos(angle) + even * np.sin(angle)], -1)
    assert rel_err(y, by_hand) < RTOL
    # and it is not the rotate-half pairing (x[i], x[i + r/2])
    (plain,), _, _ = run_piece(
        lambda d: [layers.rotary_embedding(d["x"], theta=100.0)], {"x": x})
    assert rel_err(plain, by_hand) > 0.1


# -- the router: sigmoid scores, a bias that moves the choice alone -----------------

def _route(x, w, b, k=3, **kw):
    attrs = dict(norm_topk_prob=True, score_func="sigmoid", norm_eps=1e-20,
                 scaling_factor=2.448)
    attrs.update(kw)

    def build(d):
        r = layers.moe_router(
            d["x"], w.shape[1], k, param_attr=fluid.ParamAttr(name="w"),
            bias_attr=None if b is None else _planted("b", b), **attrs)
        return [r["weight"], r["index"], r["probs"], r["tokens_per_expert"]]

    return run_piece(build, {"x": x}, {"w": w})


def test_a_planted_bias_changes_the_choice_and_not_the_weights():
    """Chosen by `s + b`, weighted by `s`: with a bias that lifts two experts
    above everything, every token goes to them (and its best other expert),
    and the weights are those experts' own sigmoids renormalised and scaled:
    `b` is nowhere in them."""
    rng = np.random.RandomState(1)
    x = rng.randn(24, 8).astype(np.float32)
    w = rng.randn(8, 16).astype(np.float32)
    b = np.zeros(16, np.float32)
    b[[5, 11]] = 10.0
    (weight, index, probs, counts), grads, probe = _route(x, w, b)
    (_, plain_index, _, _), _, _ = _route(x, w, None)
    assert np.all(np.sort(index, -1)[:, -2:] == [5, 11]) or \
        np.all((index == 5).sum(1) + (index == 11).sum(1) == 2)
    assert not np.array_equal(np.sort(index, -1), np.sort(plain_index, -1))
    assert counts[5] == 24 and counts[11] == 24 and counts.sum() == 72
    s = 1 / (1 + np.exp(-(x.astype(np.float64) @ w)))
    assert rel_err(probs, s) < 1e-5
    picked = np.take_along_axis(s, index, -1)
    want = 2.448 * picked / picked.sum(-1, keepdims=True)
    assert rel_err(weight, want) < 1e-5
    assert np.all(weight < 2.448) and np.allclose(weight.sum(-1), 2.448,
                                                  rtol=1e-5)
    with jax.default_matmul_precision("highest"):
        gx, gw = jax.grad(
            lambda a, c: jnp.sum(ref.route(a, c, jnp.asarray(b), 3, 2.448)[0]
                                 * probe), (0, 1))(x, w)
    assert rel_err(grads["x"], gx) < 1e-4 and rel_err(grads["w"], gw) < 1e-4


def test_sigmoid_router_without_a_bias_is_the_reference_at_zero_bias():
    rng = np.random.RandomState(2)
    x = rng.randn(24, 8).astype(np.float32)
    w = rng.randn(8, 16).astype(np.float32)
    (weight, index, _, _), _, _ = _route(x, w, None)
    with jax.default_matmul_precision("highest"):
        want, want_index, _ = ref.route(x, w, jnp.zeros(16), 3, 2.448)
    assert np.array_equal(index, want_index)
    assert rel_err(weight, want) < RTOL


def test_softmax_router_takes_no_new_attribute_by_default():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[8, 8], dtype="float32",
                        append_batch_size=False)
        layers.moe_router(x, 4, 2, norm_topk_prob=True)
    (op,) = [o for o in main.global_block().ops if o.type == "moe_router"]
    assert set(op.attrs) - {ir.NAME_SCOPE_ATTR} <= {"k", "norm_topk_prob"}
    assert set(op.inputs) == {"X", "W"}
    assert [o.type for o in main.global_block().ops] == ["moe_router"]


# -- the shares add up -----------------------------------------------------------------

N_EXPERT, HELD, K, D, F = 16, 2, 3, 16, 12


@pytest.mark.parametrize("path", ["ragged_dot", "pallas_interpreted"])
def test_the_eight_shares_add_up_to_the_whole_layer(path, monkeypatch):
    """The routed parts that all 8 shares give, plus the shared experts
    once, are the uncut reference's whole layer: forward, the gradient of
    the router and of the layer's input. With a planted non-zero `b`, so
    that choosing by `s + b` and weighting by `s` cannot be confused."""
    if path == "pallas_interpreted":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(5)
    x = rng.randn(40, D).astype(np.float32)
    whole = {"router.w": rng.randn(D, N_EXPERT),
             "router.bias": rng.randn(N_EXPERT) * 0.3,
             "experts.gate.w": rng.randn(N_EXPERT, D, F) * 0.3,
             "experts.up.w": rng.randn(N_EXPERT, D, F) * 0.3,
             "experts.down.w": rng.randn(N_EXPERT, F, D) * 0.3,
             "shared.gate.w": rng.randn(D, 2 * F) * 0.3,
             "shared.up.w": rng.randn(D, 2 * F) * 0.3,
             "shared.down.w": rng.randn(2 * F, D) * 0.3}
    whole = {n: v.astype(np.float32) for n, v in whole.items()}
    shares = N_EXPERT // HELD
    cut = {f"s{j}.{which}.w":
           whole[f"experts.{which}.w"][j * HELD:(j + 1) * HELD]
           for j in range(shares) for which in ("gate", "up", "down")}

    def build(d):
        routing = layers.moe_router(
            d["x"], N_EXPERT, K, norm_topk_prob=True, score_func="sigmoid",
            norm_eps=1e-20, scaling_factor=2.448,
            param_attr=fluid.ParamAttr(name="router.w"),
            bias_attr=_planted("router.bias", whole["router.bias"]))
        parts = [layers.moe_experts(
            d["x"], routing, N_EXPERT, F, name=f"s{j}",
            first_expert=j * HELD, experts_held=HELD)
            for j in range(shares)]

        def fc(v, size, name):
            return layers.fc(v, size, bias_attr=False,
                             param_attr=fluid.ParamAttr(name=name))

        hidden = layers.swiglu(fc(d["x"], 2 * F, "shared.gate.w"),
                               fc(d["x"], 2 * F, "shared.up.w"))
        return [layers.sums(parts + [fc(hidden, D, "shared.down.w")])] + parts

    params = {**{n: v for n, v in whole.items()
                 if not n.startswith(("experts.", "router.bias"))}, **cut}
    outs, grads, probe = run_piece(build, {"x": x}, params)
    kw = dict(top_k=K, routed_scaling_factor=2.448)

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
        # and each share alone is the reference given that share
        for j, part in enumerate(outs[1:]):
            held = {n: (v[j * HELD:(j + 1) * HELD]
                        if n.startswith("experts.") else v)
                    for n, v in whole.items()}
            alone = ref.sparse_experts(held, x, first_expert=j * HELD,
                                       **kw)[0]
            assert rel_err(part, alone - shared) < 1e-4, j
        # the bias mattered: at b = 0 the layer is another function
        unbiased = ref.sparse_experts(
            {**whole, "router.bias": np.zeros(N_EXPERT, np.float32)}, x,
            first_expert=0, **kw)[0]
        assert rel_err(unbiased, want(x, whole["router.w"])) > 0.05
    assert rel_err(grads["x"], gx) < 1e-4
    assert rel_err(grads["router.w"], gr) < 1e-4


# -- the model ----------------------------------------------------------------------------

def _seeded_values(shapes, seed=3):
    """Weights far from their initial values, so that no term of the
    comparison is small by construction: norm weights in [0.5, 1.5], a
    router five times as sharp, a planted bias of std 0.2 (the sigmoids'
    spread is about 0.25), matrices of std 0.1 (five times the initial)."""
    rng = np.random.RandomState(seed)
    values = {}
    for name in sorted(shapes):
        shape = shapes[name]
        if name.endswith("router.bias"):
            value = rng.randn(*shape) * 0.2
        elif "norm" in name:
            value = rng.uniform(0.5, 1.5, shape)
        elif name.endswith("router.w"):
            value = rng.randn(*shape) * 0.5
        else:
            value = rng.randn(*shape) * 0.1
        values[name] = value.astype(np.float32)
    return values


FETCHES = ["loss", "ce", "logits", "tokens_per_expert"]
BIASES = ["l1.router.bias", "l2.router.bias"]
CASE = DecoderCase(models.kanana2.build, TINY, ref, REF_KW, FETCHES,
                   state=BIASES, seeded_values=_seeded_values)


@pytest.fixture(scope="module")
def tiny():
    return CASE.tiny_model()


MLA = ["in_norm.w", "post_norm.w", "mla.q.w", "mla.kv_a.w", "mla.kv_norm.w",
       "mla.kv_b.w", "mla.o.w"]
DENSE = ["mlp.gate.w", "mlp.up.w", "mlp.down.w"]
MOE = ["router.w", "experts.gate.w", "experts.up.w", "experts.down.w",
       "shared.gate.w", "shared.up.w", "shared.down.w"]
TRAINED = (["embed.w", "final_norm.w", "head.w"]
           + [f"l{i}.{n}" for i in range(3)
              for n in MLA + (DENSE if i == 0 else MOE)])


def test_tiny_model_has_the_reference_parameters(tiny):
    CASE.has_the_reference_parameters(tiny, TRAINED, {
        "l1.experts.gate.w": (4, 32, 16), "l1.router.w": (32, 16),
        "l1.router.bias": (16,), "l0.mla.q.w": (32, 4 * (16 + 8)),
        "l0.mla.kv_a.w": (32, 16 + 8), "l0.mla.kv_b.w": (16, 4 * (16 + 16)),
        "l1.shared.gate.w": (32, 2 * 16), "l0.mlp.gate.w": (32, 48)})


@pytest.mark.parametrize("name", FETCHES)
def test_tiny_model_output_matches_reference(tiny, name):
    CASE.output_matches_reference(tiny, name)


def test_tiny_routing_sends_most_assignments_elsewhere(tiny):
    CASE.routing_sends_most_assignments_elsewhere(tiny, routed_layers=2)


@pytest.mark.parametrize("name", TRAINED)
def test_tiny_model_gradient_matches_reference(tiny, name):
    CASE.gradient_matches_reference(tiny, name)


@pytest.mark.parametrize("layer", [1, 2])
def test_one_step_moves_the_bias_as_next_bias_does(tiny, layer):
    CASE.one_step_moves_the_bias_as_next_bias_does(
        tiny, f"l{layer}.router.bias", GAMMA)


def test_reference_in_blocks_is_the_reference(tiny):
    CASE.reference_in_blocks_is_the_reference(
        tiny, ["l0.mla.q.w", "l1.mla.kv_b.w", "l2.router.w", "embed.w"],
        q_block=32)


def test_reference_last_positions_equal_the_full_pass(tiny):
    CASE.reference_last_positions_equal_the_full_pass(tiny)


def test_reference_in_bfloat16_is_another_number(tiny):
    CASE.reference_in_bfloat16_is_another_number(tiny)


# -- the bias as state -------------------------------------------------------------------

@pytest.mark.parametrize("amp", [False, True])
def test_three_adam_steps_move_the_bias_exactly(amp, tmp_path):
    """`b` after three steps is `next_bias` applied three times to the
    system's own counts, bit for bit; it has no gradient and no moments,
    stays float32 under AMP, and a checkpoint carries it."""
    main, startup, fetches, _ = CASE.program(
        fluid.optimizer.Adam(learning_rate=1e-3))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace(), amp=amp)
    exe.run(startup, scope=scope)
    assert np.all(np.asarray(scope.find_var("l1.router.bias")) == 0)
    want = {n: np.zeros(16, np.float32) for n in BIASES}
    for step in range(3):
        (counts,) = exe.run(main, feed=CASE.batch(step),
                            fetch_list=[fetches["tokens_per_expert"]],
                            scope=scope)
        for i, n in enumerate(BIASES):
            want[n] = np.asarray(ref.next_bias(want[n], counts[i], GAMMA))
            assert np.array_equal(
                np.asarray(scope.find_var(n + ".load")), counts[i])
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
    assert block.var("l1.router.bias").trainable is False
    assert block.var("l1.router.bias").persistable
    # saved with the weights, loaded into a fresh scope
    io.save_persistables(exe, str(tmp_path), main_program=main, scope=scope)
    fresh = fluid.Scope()
    exe.run(startup, scope=fresh)
    assert np.all(np.asarray(fresh.find_var("l2.router.bias")) == 0)
    io.load_persistables(exe, str(tmp_path), main_program=main, scope=fresh)
    for n in BIASES:
        assert np.array_equal(np.asarray(fresh.find_var(n)), want[n])


def test_the_backward_pass_differentiates_the_choice_the_forward_made():
    """The update overwrites `b` before the grad ops run, and a grad op
    reads the scope's values: the router reads a copy taken before. With a
    huge update rate, after which the overwritten bias would choose other
    experts, the router's gradient is still the reference's at the old b."""
    _, params, feed, _, grads, after = CASE.run_tiny(
        amp=False, bias_update_rate=5.0, n_layer=2)
    assert np.abs(after["l1.router.bias"]).max() > 4
    _, want = ref.loss_and_grads(
        params, jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"]),
        wrt=["l1.router.w"], **{**REF_KW, "n_layer": 2})
    assert frob(grads["l1.router.w"], want["l1.router.w"]) < 2e-4


def test_tiny_model_amp_within_bf16_of_reference():
    """Under AMP the residual stream, the projections, attention and the
    experts are bf16; the router's scores, `b`, every norm's statistics and
    rotary's trigonometry stay float32. At the initial weights (a sharper
    router flips a few assignments under bf16 inputs)."""
    # a routed expert's gradient feels every assignment that a bf16 input
    # flips to another expert (a whole row of it)
    CASE.amp_within_bf16_of_reference(
        {0.04: ("l0.mla.q.w", "l0.mla.kv_a.w", "l0.mla.kv_b.w", "l2.mla.o.w",
                "l0.mlp.gate.w", "l1.shared.up.w", "embed.w"),
         0.08: ("l1.experts.gate.w",)})


def test_amp_lists_hold_the_router_and_attention():
    assert "moe_router" in registry.AMP_F32_OPS
    assert "reduce_mean" in registry.AMP_F32_OPS
    assert "fused_attention" in registry.AMP_BF16_OPS
    for op in ("assign", "sign", "scale", "cast", "sum", "rotary_embedding",
               "rms_norm"):
        assert op not in registry.AMP_F32_OPS | registry.AMP_BF16_OPS


def test_five_adam_steps_lower_the_loss():
    CASE.adam_steps_lower_the_loss()


def test_attention_out_has_the_value_width():
    main, _, _, _ = CASE.program(n_layer=1)
    block = main.global_block()
    (op,) = [o for o in block.ops if o.type == "fused_attention"]
    assert block.var(op.input("Q")[0]).shape[1:] == (4, 128, 24)
    assert block.var(op.input("K")[0]).shape[1:] == (4, 128, 24)
    assert block.var(op.input("V")[0]).shape[1:] == (4, 128, 16)
    assert block.var(op.output("Out")[0]).shape[1:] == (4, 128, 16)
    assert op.attrs["sm_scale"] == 24 ** -0.5


# -- spans and counters ---------------------------------------------------------------------

def test_compile_event_carries_the_census():
    carries_the_census(CASE.compile_detail(), {
        "layer_kinds": {"latent_attention": 3}, "attention_qk_width": 24,
        "attention_value_width": 16, "dense_ffn_layers": 1,
        "moe_router_score": "sigmoid", "moe_router_bias_updates": 2,
        "moe_experts_routed": 16, "moe_experts_held": 4,
        "moe_row_buffer_rows": 2 * 128 * 3 + 4 * 128,
        "moe_share_bounded_moves": 2 * 4}, startup_lacks=["layer_kinds"])


def test_every_layer_is_built_under_its_name_scopes(tiny):
    layers_are_built_under_their_scopes(
        tiny["main"],
        ["l0.mla", "l1.mla", "l2.mla", "l0.mlp", "l1.moe", "l2.moe"],
        absent=["l0.moe", "l1.mlp"],
        holds={"l1.mla": ["fused_attention", "rotary_embedding", "concat",
                          "expand", "rms_norm"], "l0.mlp": ["swiglu"],
               "l2.moe": ["moe_router", "moe_dispatch", "grouped_matmul",
                          "moe_combine", "sign", "assign"]})


# -- the others are what they were -------------------------------------------------------------

@pytest.mark.parametrize("model", ["olmoe", "qwen3_next"])
def test_softmax_routed_programs_are_unchanged_op_for_op(model):
    """The router took a score function, a bias and a scaling factor,
    rotary an interleaved pairing and `fused_attention` a value width in
    this file's PR; a program that passes none of them is the program it
    was (`decoder_case.DIGESTS`)."""
    main, startup, _, _ = build_program(model)
    assert program_digest(main, startup) == DIGESTS[model]
    routers = [o for o in main.global_block().ops if o.type == "moe_router"]
    assert routers and all(
        set(o.inputs) == {"X", "W"} and not
        {"score_func", "norm_eps", "scaling_factor"} & set(o.attrs)
        for o in routers)


def test_the_two_copies_of_the_reference_are_identical():
    CASE.two_copies_of_the_reference_are_identical()


def test_the_tiny_block_runs_through_the_benchmark():
    runs_through_the_benchmark("kanana_2_30b_a3b.bs1")
