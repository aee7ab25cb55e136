"""Mellum2 (sliding-window and full causal attention layers mixed 3:1 through
`fused_attention(window=...)`, 32/4-style grouped heads, plain rotary on the
window layers and YaRN on the full ones, one chip's share of a renormalised
top-k expert layer in every layer) through `layers` -> Program IR ->
`Executor`, against the plain reference (`tests/mellum2_reference.py`: a
masked softmax whose mask is two inequalities, `jnp.repeat`, YaRN from its
formulas, a loop over the held experts). Seeded random weights, float32, AMP
off unless a test says otherwise."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import io, layers, models
from paddle_tpu.core import ir, registry
from paddle_tpu.ops import decoder_block

import mellum2_reference as ref
from decoder_case import (DIGESTS, TINY_YARN, DecoderCase, build_program,
                          carries_the_census, frob,
                          layers_are_built_under_their_scopes, program_digest,
                          rel_err, run_piece, runs_through_the_benchmark,
                          tiny_args)

TINY = tiny_args("mellum2")
REF_KW = {k: TINY[k] for k in (
    "n_layer", "n_head", "n_kv_head", "head_dim", "sliding_window",
    "rope_theta", "rope_scaling", "top_k", "first_expert")}
RTOL = 2e-5
PUBLISHED_YARN = models.mellum2.YARN


# -- YaRN's tables ----------------------------------------------------------------

def _yarn_by_hand(dim, theta, s):
    """The issue's formulas in numpy float64."""
    j = np.arange(dim // 2, dtype=np.float64)
    pos = theta ** (2 * j / dim)
    length = s["original_max_position_embeddings"]
    c = lambda r: dim * math.log(length / (2 * math.pi * r)) \
        / (2 * math.log(theta))
    low = max(math.floor(c(s["beta_fast"])), 0)
    high = min(math.ceil(c(s["beta_slow"])), dim - 1)
    ramp = np.clip((j - low) / (high - low), 0, 1)
    return ramp / (s["factor"] * pos) + (1 - ramp) / pos, low, high


@pytest.mark.parametrize("dim,low,high", [(128, 18, 35), (64, 9, 18)])
def test_yarn_frequencies_are_the_formulas(dim, low, high):
    """`rotary_frequencies` under the published block at the published head
    size (low 18, high 35, as the issue works out) and at half of it; the
    reference's own `yarn_frequencies` agrees; the dims below `low` keep
    their frequency and those above `high` have it divided by 16."""
    want, got_low, got_high = _yarn_by_hand(dim, 5e5, PUBLISHED_YARN)
    assert (got_low, got_high) == (low, high)
    inv_freq, factor = decoder_block.rotary_frequencies(dim, 5e5,
                                                        PUBLISHED_YARN)
    assert factor == 1.2772588722239782
    np.testing.assert_allclose(np.asarray(inv_freq), want, rtol=1e-5)
    theirs, their_factor = ref.yarn_frequencies(dim, 5e5, PUBLISHED_YARN)
    np.testing.assert_allclose(np.asarray(theirs), want, rtol=1e-5)
    assert their_factor == factor
    plain, one = decoder_block.rotary_frequencies(dim, 5e5)
    assert one == 1.0
    np.testing.assert_allclose(np.asarray(inv_freq[:low + 1]),
                               np.asarray(plain[:low + 1]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv_freq[high:]) * 16,
                               np.asarray(plain[high:]), rtol=1e-5)
    assert np.all(np.asarray(inv_freq[low + 1:high]) <
                  np.asarray(plain[low + 1:high]))


def test_yarn_attention_factor_defaults_to_a_tenth_of_ln_factor_plus_one():
    block = {k: v for k, v in PUBLISHED_YARN.items()
             if k != "attention_factor"}
    _, factor = decoder_block.rotary_frequencies(128, 5e5, block)
    assert factor == pytest.approx(0.1 * math.log(16) + 1)
    assert factor == pytest.approx(PUBLISHED_YARN["attention_factor"])


@pytest.mark.parametrize("scaling", [None, TINY_YARN],
                         ids=["plain", "yarn"])
def test_rotary_op_is_the_reference_in_both_regimes(scaling):
    rng = np.random.RandomState(0)
    x = rng.randn(2, 3, 32, 16).astype(np.float32)
    (y,), grads, probe = run_piece(
        lambda d: [layers.rotary_embedding(d["x"], theta=1e4,
                                           scaling=scaling)], {"x": x})
    want = ref.rotary(jnp.asarray(x), 1e4, scaling)
    assert rel_err(y, want) < RTOL
    gx = jax.grad(lambda a: jnp.sum(ref.rotary(a, 1e4, scaling) * probe))(
        jnp.asarray(x))
    assert rel_err(grads["x"], gx) < RTOL
    if scaling:     # and it is another function than plain rotary
        assert rel_err(y, ref.rotary(jnp.asarray(x), 1e4)) > 0.1


def test_rotary_without_scaling_takes_no_new_attribute():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[1, 2, 8, 16], dtype="float32",
                        append_batch_size=False)
        layers.rotary_embedding(x, theta=1e4)
        layers.rotary_embedding(x, theta=1e4, scaling=PUBLISHED_YARN)
        with pytest.raises(ValueError, match="YaRN block"):
            layers.rotary_embedding(x, scaling={"factor": 2.0})
        with pytest.raises(ValueError, match="YaRN block"):
            layers.rotary_embedding(x, scaling=dict(PUBLISHED_YARN, mscale=1))
    plain, scaled = main.global_block().ops
    assert set(plain.attrs) - {ir.NAME_SCOPE_ATTR} == {"theta"}
    assert scaled.attrs["scaling"] == {k: float(v)
                                       for k, v in PUBLISHED_YARN.items()}


# -- the shares add up -----------------------------------------------------------------

N_EXPERT, HELD, K, D, F = 16, 2, 3, 16, 12


@pytest.mark.parametrize("path", ["ragged_dot", "pallas_interpreted"])
def test_the_eight_shares_add_up_to_the_whole_layer(path, monkeypatch):
    """The routed parts that all 8 shares give are the uncut reference's
    whole layer (softmax over all 16, top-3 renormalised over all three
    chosen, whichever share holds them): forward, the gradient of the router
    and of the layer's input; and each share alone is the reference given
    that share."""
    if path == "pallas_interpreted":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(5)
    x = rng.randn(40, D).astype(np.float32)
    whole = {"router.w": rng.randn(D, N_EXPERT),
             "experts.gate.w": rng.randn(N_EXPERT, D, F) * 0.3,
             "experts.up.w": rng.randn(N_EXPERT, D, F) * 0.3,
             "experts.down.w": rng.randn(N_EXPERT, F, D) * 0.3}
    whole = {n: v.astype(np.float32) for n, v in whole.items()}
    shares = N_EXPERT // HELD
    cut = {f"s{j}.{which}.w":
           whole[f"experts.{which}.w"][j * HELD:(j + 1) * HELD]
           for j in range(shares) for which in ("gate", "up", "down")}

    def build(d):
        routing = layers.moe_router(
            d["x"], N_EXPERT, K, norm_topk_prob=True,
            param_attr=fluid.ParamAttr(name="router.w"))
        parts = [layers.moe_experts(
            d["x"], routing, N_EXPERT, F, name=f"s{j}",
            first_expert=j * HELD, experts_held=HELD)
            for j in range(shares)]
        return [layers.sums(parts)] + parts

    outs, grads, probe = run_piece(
        build, {"x": x}, {"router.w": whole["router.w"], **cut})

    def want(x, router_w):
        return ref.sparse_experts({**whole, "router.w": router_w}, x,
                                  top_k=K, first_expert=0)[0]

    with jax.default_matmul_precision("highest"):
        assert rel_err(outs[0], want(x, whole["router.w"])) < RTOL
        gx, gr = jax.grad(lambda a, b: jnp.sum(want(a, b) * probe),
                          (0, 1))(x, whole["router.w"])
        for j, part in enumerate(outs[1:]):
            held = {n: (v[j * HELD:(j + 1) * HELD]
                        if n.startswith("experts.") else v)
                    for n, v in whole.items()}
            alone = ref.sparse_experts(held, x, top_k=K,
                                       first_expert=j * HELD)[0]
            assert rel_err(part, alone) < 1e-4, j
            assert float(jnp.abs(alone).max()) > 0
    assert rel_err(grads["x"], gx) < 1e-4
    assert rel_err(grads["router.w"], gr) < 1e-4


# -- the model ----------------------------------------------------------------------------

def _seeded_values(shapes, seed=3):
    """Weights far from their initial values, so that no term of the
    comparison is small by construction: norm weights in [0.5, 1.5], a
    router five times as sharp, matrices of std 0.1 (five times the
    initial)."""
    rng = np.random.RandomState(seed)
    values = {}
    for name in sorted(shapes):
        shape = shapes[name]
        if "norm" in name:
            value = rng.uniform(0.5, 1.5, shape)
        elif name.endswith("router.w"):
            value = rng.randn(*shape) * 0.5
        else:
            value = rng.randn(*shape) * 0.1
        values[name] = value.astype(np.float32)
    return values


FETCHES = ["loss", "ce", "load_balance", "logits", "tokens_per_expert"]
# what each planted fault has to move, at least: the loss by 1e-4 or a
# gradient by 1% where the true reference is met within 2e-4
FAULT_WRT = ["l0.attn.q.w", "l0.attn.k.w", "l0.attn.v.w", "l3.attn.q.w",
             "l3.attn.k.w"]
CASE = DecoderCase(models.mellum2.build, TINY, ref, REF_KW, FETCHES,
                   seeded_values=_seeded_values, fault_wrt=FAULT_WRT)


@pytest.fixture(scope="module")
def tiny():
    return CASE.tiny_model()


LAYER = ["in_norm.w", "post_norm.w", "attn.q.w", "attn.k.w", "attn.v.w",
         "attn.q_norm.w", "attn.k_norm.w", "attn.o.w", "router.w",
         "experts.gate.w", "experts.up.w", "experts.down.w"]
TRAINED = (["embed.w", "final_norm.w", "head.w"]
           + [f"l{i}.{n}" for i in range(4) for n in LAYER])


def test_tiny_model_has_the_reference_parameters(tiny):
    CASE.has_the_reference_parameters(tiny, TRAINED, {
        "l0.attn.q.w": (32, 4 * 16), "l0.attn.k.w": (32, 2 * 16),
        "l3.attn.v.w": (32, 2 * 16), "l0.attn.q_norm.w": (16,),
        "l1.experts.gate.w": (4, 32, 16), "l1.router.w": (32, 16)})


@pytest.mark.parametrize("name", FETCHES)
def test_tiny_model_output_matches_reference(tiny, name):
    CASE.output_matches_reference(tiny, name)


@pytest.mark.parametrize("name", TRAINED)
def test_tiny_model_gradient_matches_reference(tiny, name):
    CASE.gradient_matches_reference(tiny, name)


@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_each_planted_fault_is_refused(tiny, fault):
    """A fault on one kind of layer leaves the other kind's rotary and mask
    alone, but everything downstream still feels it: the loss moves too."""
    CASE.planted_fault_is_refused(tiny, fault, loss=1e-4)


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
        wrt=["l0.attn.q.w", "l1.attn.k.w", "l2.attn.v.w", "l3.attn.q.w"],
        last=TINY["seq_len"], **REF_KW)
    assert rel_err(got["logits"], want["logits"]) < 1e-4
    for name, g in want_grads.items():
        assert frob(grads[name], g) < 2e-4, name


def test_reference_in_blocks_is_the_reference(tiny):
    CASE.reference_in_blocks_is_the_reference(
        tiny, ["l0.attn.q.w", "l3.attn.k.w", "l2.router.w", "embed.w"],
        q_block=32)


def test_reference_last_positions_equal_the_full_pass(tiny):
    CASE.reference_last_positions_equal_the_full_pass(tiny)


def test_reference_in_bfloat16_is_another_number(tiny):
    CASE.reference_in_bfloat16_is_another_number(tiny)


def test_layer_types_repeat_as_a_period():
    kinds = models.mellum2.layer_kinds
    assert kinds(8) == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert kinds(3, ["full_attention", "sliding_attention"]) == [
        "full_attention", "sliding_attention", "full_attention"]
    assert models.mellum2.PERIOD == ref.PERIOD
    with pytest.raises(ValueError, match="layer_types holds"):
        kinds(2, ["sliding_attention", "linear_attention"])
    main, _, _, _ = CASE.program(n_layer=2, layer_types=["full_attention",
                                                     "sliding_attention"])
    windows = [op.attrs.get("window") for op in main.global_block().ops
               if op.type == "fused_attention"]
    assert windows == [None, 96]


def test_tiny_model_amp_within_bf16_of_reference():
    """Under AMP the residual stream, the projections, attention and the
    experts are bf16; the router, every norm's statistics and rotary's
    trigonometry stay float32. At the initial weights (a sharper router
    flips a few assignments under bf16 inputs)."""
    CASE.amp_within_bf16_of_reference(
        {0.04: ("l0.attn.q.w", "l0.attn.k.w", "l0.attn.v.w",
                "l0.attn.q_norm.w", "l3.attn.q.w", "l3.attn.k.w", "embed.w"),
         0.08: ("l1.experts.gate.w",)}, most=0.15)


def test_five_adam_steps_lower_the_loss():
    CASE.adam_steps_lower_the_loss()


def test_save_and_load_carry_the_weights_and_the_new_attributes(tmp_path):
    """A checkpoint into a fresh scope gives the same loss; the program
    written out and parsed back keeps `window` and `scaling`, and runs to
    the same loss."""
    main, startup, fetches, _ = CASE.program(
        fluid.optimizer.Adam(learning_rate=1e-3))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = CASE.batch()
    exe.run(main, feed=feed, fetch_list=[fetches["loss"]], scope=scope)
    io.save_persistables(exe, str(tmp_path), main_program=main, scope=scope)
    (loss,) = exe.run(main, feed=feed, fetch_list=[fetches["loss"]],
                      scope=scope)
    fresh = fluid.Scope()
    exe.run(startup, scope=fresh)
    io.load_persistables(exe, str(tmp_path), main_program=main, scope=fresh)
    (again,) = exe.run(main, feed=feed, fetch_list=[fetches["loss"]],
                       scope=fresh)
    assert np.array_equal(loss, again)
    parsed = fluid.Program.parse_from_string(main.serialize_to_string())
    attention = [op for op in parsed.global_block().ops
                 if op.type == "fused_attention"]
    assert [op.attrs.get("window") for op in attention] == [96, 96, 96, None]
    rotary = [op.attrs.get("scaling") for op in parsed.global_block().ops
              if op.type == "rotary_embedding"]
    assert rotary == [None] * 6 + [TINY_YARN] * 2
    third = fluid.Scope()
    exe.run(startup, scope=third)
    io.load_persistables(exe, str(tmp_path), main_program=main, scope=third)
    (parsed_loss,) = exe.run(parsed, feed=feed,
                             fetch_list=[fetches["loss"].name], scope=third)
    assert np.array_equal(loss, parsed_loss)


def test_attention_ops_have_the_groups_shapes():
    main, _, _, _ = CASE.program(n_layer=1)
    block = main.global_block()
    (op,) = [o for o in block.ops if o.type == "fused_attention"]
    for slot in ("Q", "K", "V"):
        assert block.var(op.input(slot)[0]).shape[1:] == (4, 256, 16)
    assert op.attrs["sm_scale"] == 16 ** -0.5 and op.attrs["window"] == 96
    expands = [o for o in block.ops if o.type == "expand"]
    assert [o.attrs["expand_times"] for o in expands] == [[1, 1, 2, 1, 1]] * 2


# -- spans and counters ---------------------------------------------------------------------

def test_compile_event_carries_the_census():
    carries_the_census(CASE.compile_detail(), {
        "layer_kinds": {"window_attention": 3, "full_attention": 1},
        "attention_window_layers": 3, "attention_window": 96,
        "attention_kv_group": 2, "moe_experts_routed": 16,
        "moe_experts_held": 4, "moe_share_bounded_moves": 4 * 4,
        # batch 2 x 4 heads x 3 layers, 128 x 128 tiles under a window of
        # 96: all three of the triangle's meet the band
        "window_tiles_computed": 72}, startup_lacks=["layer_kinds"])


def test_the_tally_follows_the_tiles(monkeypatch):
    """At tiles of 128 a window of 96 over 512 tokens meets 7 of the
    triangle's 10 tiles: the counter
    is the forward grid's, summed over the windowed ops, and a full layer
    adds nothing to it."""
    from paddle_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (128, 128))
    detail, _ = CASE.compile_detail(seq_len=512, n_layer=4)
    assert detail["window_tiles_computed"] == 3 * 2 * 4 * 7


@pytest.mark.parametrize("window,windowed,band", [(96, 0, 7), (300, 3, 10)])
def test_the_unmasked_tally_follows_the_tiles(monkeypatch, window, windowed,
                                              band):
    """`flash_tiles_unmasked`: the tiles that run without the causal mask,
    summed over the windowed ops, the grad ops' traces adding nothing. At
    tiles of 128 over 512 tokens a window of 96 is narrower than a tile and
    has none, a window of 300 holds the three next to the diagonal; the
    full layer's six under the diagonal keep the mask (a plain causal call:
    `_interior_apart`) and add nothing."""
    from paddle_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", (128, 128))
    assert pallas_attention.interior_tiles(512) == 6
    assert pallas_attention.interior_tiles(512, window) == windowed
    carries_the_census(
        CASE.compile_detail(seq_len=512, n_layer=4, sliding_window=window),
        {"window_tiles_computed": 3 * 2 * 4 * band,
         "flash_tiles_unmasked": 2 * 4 * 3 * windowed,
         # the full layer's grid has six steps above the diagonal, a head
         "flash_dead_steps_held": 2 * 4 * 1 * 6},
        startup_lacks=["flash_tiles_unmasked"])


def test_a_window_over_the_whole_sequence_is_counted_as_full():
    detail, _ = CASE.compile_detail(sliding_window=256, n_layer=2,
                                    layer_types=["sliding_attention"])
    assert detail["layer_kinds"] == {"full_attention": 2}
    assert "attention_window_layers" not in detail


def test_every_layer_is_built_under_its_name_scopes(tiny):
    mixer = ["fused_attention", "rotary_embedding", "expand", "rms_norm",
             "mul"]
    layers_are_built_under_their_scopes(
        tiny["main"],
        ["l0.swa", "l1.swa", "l2.swa", "l3.attn", "l0.moe", "l3.moe"],
        absent=["l3.swa", "l0.attn"],
        holds={"l0.swa": mixer, "l3.attn": mixer,
               "l2.moe": ["moe_router", "moe_dispatch", "grouped_matmul",
                          "moe_combine"]})


def test_amp_lists_hold_the_router_and_attention():
    assert "moe_router" in registry.AMP_F32_OPS
    assert "fused_attention" in registry.AMP_BF16_OPS
    for op in ("rotary_embedding", "rms_norm", "expand"):
        assert op not in registry.AMP_F32_OPS | registry.AMP_BF16_OPS


# -- the others are what they were -------------------------------------------------------------

@pytest.mark.parametrize("model", ["olmoe", "qwen3_next", "kanana2"])
def test_programs_without_a_window_are_unchanged_op_for_op(model):
    """`fused_attention` took a window and `rotary_embedding` a scaling
    block in this file's PR; a program that passes neither is the program
    it was, op for op and attribute for attribute
    (`decoder_case.DIGESTS`)."""
    main, startup, _, _ = build_program(model)
    assert program_digest(main, startup) == DIGESTS[model]
    for op in main.global_block().ops:
        assert "window" not in op.attrs and "scaling" not in op.attrs


def test_the_two_copies_of_the_reference_are_identical():
    CASE.two_copies_of_the_reference_are_identical()


def test_the_tiny_block_runs_through_the_benchmark():
    runs_through_the_benchmark("mellum2_12b_a2_5b.s8192")
