"""Trinity-Mini (window layers that turn by rotary beside full layers with no
positions, grouped heads, QK-norm, an output gate from a projection of its
own, four norms a layer, a scaled embedding, a leading dense layer, one chip's
share of a sigmoid-routed expert layer whose selection bias the step rewrites,
a shared expert) through `layers` -> Program IR -> `Executor`, against the
plain reference (`tests/trinity_reference.py`: a masked softmax whose mask is
two inequalities, `jnp.repeat`, a loop over the held experts, `next_bias`).
The sizes are the configuration's `tiny` block. Seeded random weights,
float32, AMP off unless a test says otherwise."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models
from paddle_tpu.core import ir, registry

import trinity_reference as ref
from decoder_case import (DecoderCase, _forward_ops_by_scope, _planted,
                          carries_the_census, config, frob,
                          layers_are_built_under_their_scopes, rel_err,
                          run_piece, runs_through_the_benchmark, tiny_args)

CONFIG = config("trinity")
GAMMA = 0.001
# 5 layers (one dense, then sliding x 3 and full, all but the first over
# experts), hidden 64, 4/2 heads of 32, window 96 over 256 tokens, 16 experts
# top-4, 4 held from expert 4
TINY = tiny_args("trinity")
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

def _seeded_values(shapes, seed=3):
    """Weights far from their initial values, so that no term of the
    comparison is small by construction: norm weights in [0.5, 1.5], a router
    five times as sharp, a planted bias of std 0.2 (the sigmoids' spread is
    about 0.25), an embedding of std 0.02 (scaled by 8 in the model), the
    other matrices of std 0.1 (five times the initial)."""
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
        elif name == "embed.w":
            value = rng.randn(*shape) * 0.02
        else:
            value = rng.randn(*shape) * 0.1
        values[name] = value.astype(np.float32)
    return values


FETCHES = ["loss", "ce", "logits", "tokens_per_expert"]
BIASES = [f"l{i}.router.bias" for i in range(1, 5)]
# what each planted fault has to move, at least: the logits or a gradient by
# 1% where the true reference is met within 2e-4
FAULT_WRT = ["l1.attn.q.w", "l1.attn.k.w", "l1.attn.gate.w", "l4.attn.q.w",
             "l4.attn.k.w", "l4.attn.gate.w", "l2.post_attn_norm.w",
             "l1.router.w", "embed.w"]
CASE = DecoderCase(models.trinity.build, TINY, ref, REF_KW, FETCHES,
                   state=BIASES, seeded_values=_seeded_values,
                   fault_wrt=FAULT_WRT)


@pytest.fixture(scope="module")
def tiny():
    return CASE.tiny_model()


MIXER = ["in_norm.w", "post_attn_norm.w", "pre_mlp_norm.w", "post_mlp_norm.w",
         "attn.q.w", "attn.k.w", "attn.v.w", "attn.gate.w", "attn.q_norm.w",
         "attn.k_norm.w", "attn.o.w"]
DENSE = ["mlp.gate.w", "mlp.up.w", "mlp.down.w"]
MOE = ["router.w", "experts.gate.w", "experts.up.w", "experts.down.w",
       "shared.gate.w", "shared.up.w", "shared.down.w"]
TRAINED = (["embed.w", "final_norm.w", "head.w"]
           + [f"l{i}.{n}" for i in range(5)
              for n in MIXER + (DENSE if i == 0 else MOE)])


def test_tiny_model_has_the_reference_parameters(tiny):
    CASE.has_the_reference_parameters(tiny, TRAINED, {
        "l0.attn.q.w": (64, 4 * 32), "l0.attn.gate.w": (64, 4 * 32),
        "l4.attn.k.w": (64, 2 * 32), "l4.attn.v.w": (64, 2 * 32),
        "l0.attn.o.w": (4 * 32, 64), "l0.attn.q_norm.w": (32,),
        "l4.attn.k_norm.w": (32,), "l1.experts.gate.w": (4, 64, 32),
        "l1.router.w": (64, 16), "l1.router.bias": (16,),
        "l1.shared.gate.w": (64, 32), "l0.mlp.gate.w": (64, 96)})


@pytest.mark.parametrize("name", FETCHES)
def test_tiny_model_output_matches_reference(tiny, name):
    CASE.output_matches_reference(tiny, name)


def test_tiny_routing_sends_most_assignments_elsewhere(tiny):
    CASE.routing_sends_most_assignments_elsewhere(tiny, routed_layers=4)


@pytest.mark.parametrize("name", TRAINED)
def test_tiny_model_gradient_matches_reference(tiny, name):
    CASE.gradient_matches_reference(tiny, name)


@pytest.mark.parametrize("layer", [1, 2, 3, 4])
def test_one_step_moves_the_bias_as_next_bias_does(tiny, layer):
    CASE.one_step_moves_the_bias_as_next_bias_does(
        tiny, f"l{layer}.router.bias", GAMMA)


@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_each_planted_fault_is_refused(tiny, fault):
    CASE.planted_fault_is_refused(tiny, fault, loss=1e-5)


def test_the_config_names_every_fault_and_no_other():
    assert sorted(CONFIG["reference"]["check"]["faults"]) == sorted(ref.FAULTS)
    assert len(ref.FAULTS) == 10


def test_an_unknown_fault_is_refused(tiny):
    CASE.unknown_fault_is_refused(tiny)


def test_interpreted_kernels_give_the_reference_too(monkeypatch):
    """The same program with the flash kernels under the Pallas interpreter
    (the windowed one-pass forward and the fused backward at 256 tokens)
    instead of the CPU path's jnp reference."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    _, params, feed, got, grads, _ = CASE.run_tiny(amp=False)
    want, want_grads = ref.loss_and_grads(
        params, jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"]),
        wrt=["l0.attn.q.w", "l1.attn.k.w", "l2.attn.v.w", "l4.attn.q.w",
             "l4.attn.gate.w"],
        last=TINY["seq_len"], **REF_KW)
    assert rel_err(got["logits"], want["logits"]) < 1e-4
    for name, g in want_grads.items():
        assert frob(grads[name], g) < 2e-4, name


def test_reference_in_blocks_is_the_reference(tiny):
    CASE.reference_in_blocks_is_the_reference(
        tiny, ["l0.attn.q.w", "l1.attn.gate.w", "l4.attn.k.w", "l2.router.w",
               "embed.w"], q_block=32)


def test_reference_last_positions_equal_the_full_pass(tiny):
    CASE.reference_last_positions_equal_the_full_pass(tiny)


def test_reference_in_bfloat16_is_another_number(tiny):
    CASE.reference_in_bfloat16_is_another_number(tiny)


# -- the bias as state -------------------------------------------------------------------

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
    assert np.all(np.asarray(scope.find_var("l1.router.bias")) == 0)
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
    assert not block.has_var("l1.router.bias@GRAD")
    state = set(scope.local_var_names())
    assert any(n.startswith("l1.router.w_moment") for n in state)
    assert not any(n.startswith("l1.router.bias_") for n in state)
    assert block.var("l4.router.bias").trainable is False
    assert block.var("l4.router.bias").persistable


# -- what the Program holds --------------------------------------------------------------------

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
    layers_are_built_under_their_scopes(
        tiny["main"],
        ["l0.swa", "l3.swa", "l4.attn", "l0.mlp", "l1.moe", "l4.moe"],
        absent=["l0.moe", "l1.mlp"],
        holds={"l1.swa": ["fused_attention", "rotary_embedding", "expand",
                          "rms_norm", "sigmoid", "elementwise_mul"],
               "l2.moe": ["moe_router", "moe_dispatch", "grouped_matmul",
                          "moe_combine", "sign", "assign", "rms_norm"]})
    # the embedding's scale is the one op between the look-up and layer 0
    first = [o.type for o in tiny["main"].global_block().ops[:3]]
    assert first[:2] == ["lookup_table", "scale"]
    scale = tiny["main"].global_block().ops[1]
    assert scale.attrs["scale"] == 8.0


def test_attention_ops_have_the_groups_shapes():
    main, _, _, _ = CASE.program()
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
    # the largest is a token whose assignment flipped: its experts' output
    # is normed to unit size on the way out, whatever its own size was; a
    # routed expert's gradient feels every assignment that a bf16 input
    # flips to another expert (a whole row of it)
    CASE.amp_within_bf16_of_reference(
        {0.08: ("l0.attn.q.w", "l1.attn.k.w", "l1.attn.gate.w",
                "l4.attn.q.w", "l4.attn.gate.w", "l0.mlp.gate.w",
                "l1.shared.up.w", "embed.w"), 0.12: ("l1.experts.gate.w",)},
        most=1.0)


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
    CASE.adam_steps_lower_the_loss()


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
    return CASE.compile_detail()


@pytest.mark.parametrize("key", sorted(CENSUS))
def test_compile_event_carries_the_census(census, key):
    carries_the_census(census, {key: CENSUS[key]})


def test_the_unmasked_tally_follows_the_tiles(monkeypatch):
    """At tiles of 128 over 512 tokens and a window of 300 a windowed layer
    runs the three tiles next to the diagonal without the causal mask:
    `flash_tiles_unmasked` sums the windowed ops, forward ops only; the full
    layer's six under the diagonal keep the mask and add nothing."""
    from paddle_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (128, 128))
    monkeypatch.setitem(TINY, "seq_len", 512)
    detail, _ = CASE.compile_detail(sliding_window=300)
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
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        getattr(models, model).build(**tiny_args(model))
    from paddle_tpu.observe import census
    got = census.layer_census(main)
    new = ("attention_rotary_layers", "attention_unrotated_layers",
           "attention_gated_layers", "residual_out_norms")
    assert {k: got[k] for k in new if k in got} == want


# -- the copies and the harness -----------------------------------------------------------------

def test_the_two_copies_of_the_reference_are_identical():
    CASE.two_copies_of_the_reference_are_identical()


def test_the_tiny_block_runs_through_the_benchmark():
    runs_through_the_benchmark("trinity_mini_26b_a3b.s4096")
