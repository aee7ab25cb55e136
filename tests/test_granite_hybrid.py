"""Granite 4.0-H (every layer a mixer AND a gated feed-forward; the mixer a
Mamba-2 state-space mixer of ONE group of heads through the chunked
`ssd_scan`, or grouped softmax attention with no positions at the published
scale; four scalar multipliers; one table as embedding and head) through
`layers` -> Program IR -> `Executor`, against the plain reference
(`tests/granite_hybrid_reference.py`: the recurrence token by token, a
convolution of shifted products plus its bias, `jnp.repeat`, the table used
twice). The sizes are the configuration's `tiny` block. Seeded random
weights, float32, AMP off unless a test says otherwise.

Tolerances: a float32 program against a float32 reference at "highest" agrees
to a few 1e-6 in a product's result; through ten layers of two sublayers and
a softmax the logits stay within 1e-4 of their largest value and a gradient
within 2e-4 in the Frobenius norm (`test_nemotron_h.py`'s limits, for its
reason). The chunked scan against the recurrence sums the same products in
another order, exponentials of differences in place of products of
exponentials: 2e-5 of the largest value (RTOL)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models
from paddle_tpu.core import registry
from paddle_tpu.observe import census
from paddle_tpu.ops import decoder_block as db
from paddle_tpu.ops import state_space as ss

import granite_hybrid_reference as ref
from decoder_case import (ROOT, SCAN_NAMES, DecoderCase,
                          _forward_ops_by_scope, _published_scan, _recurrence,
                          _scan_inputs, _scan_layer, build_program,
                          carries_the_census, config, frob, rel_err,
                          run_piece, runs_through_the_benchmark, tiny_args)

CONFIG = config("granite_hybrid")
TYPES = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# the model's own first ten layers, hidden 64, feed-forwards of 96, ONE group
# of 4 state-space heads of 16 over a state of 16, 128 tokens in chunks of 64,
# 4/2 attention heads of 16 at the published scale 1/64
TINY = tiny_args("granite_hybrid")
REF_KW = {k: TINY[k] for k in (
    "layer_types", "mamba_heads", "mamba_head_dim", "n_groups", "ssm_state",
    "n_head", "n_kv_head", "head_dim", "embedding_multiplier",
    "residual_multiplier", "attention_multiplier", "logits_scaling",
    "tie_embeddings", "rms_eps", "chunk")}
RTOL = 2e-5


def test_the_tiny_block_is_the_issues():
    assert TINY["layer_types"] == TYPES == CONFIG["layer_types"][:10]
    assert (TINY["seq_len"], TINY["chunk"], TINY["d_model"],
            TINY["d_ff"]) == (128, 64, 64, 96)
    assert (TINY["mamba_heads"], TINY["mamba_head_dim"], TINY["n_groups"],
            TINY["ssm_state"]) == (4, 16, 1, 16)
    assert (TINY["n_head"], TINY["n_kv_head"], TINY["head_dim"]) == (4, 2, 16)
    assert (TINY["embedding_multiplier"], TINY["residual_multiplier"],
            TINY["attention_multiplier"], TINY["logits_scaling"]) \
        == (12, 0.22, 0.015625, 8)
    # every size that sets the cost is overridden; what stays is no size
    kept = set(CONFIG["build_args"]) - set(CONFIG["tiny"]["build_args"])
    assert kept == {"layer_types", "n_groups", "conv_kernel", "time_step",
                    "embedding_multiplier", "residual_multiplier",
                    "attention_multiplier", "logits_scaling",
                    "tie_embeddings", "rms_eps"}


# -- the scan at one group of several heads ------------------------------------------------------

@pytest.mark.parametrize("T,chunk,heads", [
    (256, 256, 8), (512, 256, 8), (768, 256, 4), (192, 64, 8), (128, 32, 6)],
    ids=["one_chunk_of_256", "two_chunks_of_256", "three_chunks_of_256",
         "three_chunks_of_64", "four_chunks_six_heads"])
def test_one_group_of_heads_is_the_recurrence(T, chunk, heads):
    """ONE group of B and C read by every head, at the published chunk of
    256 and at chunk counts that are and are not a power of two: forward and
    every gradient of the op (the XLA form: the plan leaves these shapes to
    it) against the token-by-token recurrence, float32. B's and C's
    gradients are sums over all the heads. A gradient within 3e-4: at a chunk
    of 256 the running sum of a reaches four times what it does at 64 before
    differences are taken of it, and `A_log`'s gradient, a few numbers
    downstream of every exponent, read 1.1e-4 at three chunks of 256 (1e-4
    holds at Nemotron-H's 64 and 128)."""
    feed, params = _scan_inputs(1, T, heads, 8, 1, 16, seed=T + heads)
    assert ss._plan(8, 16, heads, chunk) == "xla"
    (y,), grads, probe = run_piece(_scan_layer(chunk), feed, params)
    args = [jnp.asarray({**feed, **params}[n]) for n in SCAN_NAMES]
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*args)
        want_grads = jax.grad(
            lambda *a: jnp.sum(_recurrence(*a) * probe),
            range(len(args)))(*args)
    assert rel_err(y, want) < RTOL
    for name, g in zip(SCAN_NAMES, want_grads):
        assert frob(grads[name], g) < 3e-4, name


def test_the_recurrence_does_not_depend_on_the_chunk():
    feed, params = _scan_inputs(1, 512, 4, 8, 1, 16, seed=1)
    runs = [run_piece(_scan_layer(chunk), feed, params)
            for chunk in (64, 128, 256)]
    for other in runs[1:]:
        assert rel_err(runs[0][0][0], other[0][0]) < RTOL
        for name in SCAN_NAMES:
            assert frob(runs[0][1][name], other[1][name]) < 3e-4, name


def test_one_group_is_not_a_group_a_head():
    """With one group every head reads the same B and C: the same inputs
    read as a group a head (B and C tiled) agree, and with another B for the
    other heads they do not."""
    feed, params = _scan_inputs(1, 128, 4, 8, 1, 16, seed=2)
    one = run_piece(_scan_layer(64), feed, params)[0][0]
    tiled = {**feed, "b": np.tile(feed["b"], (1, 1, 4, 1)),
             "c": np.tile(feed["c"], (1, 1, 4, 1))}
    assert rel_err(run_piece(_scan_layer(64), tiled, params)[0][0], one) < RTOL
    tiled["b"] = tiled["b"] * np.array([1, 2, 3, 4], np.float32)[:, None]
    assert rel_err(run_piece(_scan_layer(64), tiled, params)[0][0], one) > 0.1


@pytest.mark.parametrize("P,N,r,chunk,plan", [
    (64, 128, 64, 256, "kernel"),   # Granite 4.0-H: chunk 256, 64 heads
    (64, 128, 8, 256, "kernel"),    # Nemotron-H's group at that chunk
    (64, 128, 8, 128, "kernel"),    # Nemotron-H
    (64, 128, 12, 128, "xla"),      # more than 8 heads, no whole blocks of 8
    (64, 128, 8, 192, "xla"),       # a chunk that is no whole steps of 128
    (16, 16, 4, 64, "xla")],        # the tiny block
    ids=["granite", "chunk_256", "nemotron", "twelve_heads", "chunk_192",
         "tiny"])
def test_the_plan_reads_the_shape_alone(P, N, r, chunk, plan):
    assert ss._plan(P, N, r, chunk) == plan


def test_the_published_scan_takes_the_kernels(monkeypatch):
    """At the published shape on a chip the op takes the kernel pair, eight
    blocks of 8 heads in steps of 128 tokens under the chunk attribute of
    256, and a Program built on a machine without a TPU declares `States`
    as the chip's kernels write it: a state every 128 tokens."""
    from paddle_tpu.ops import _kernels
    assert not ss._kernels_run(64, 128, 64, 256)        # a CPU, no interpreter
    monkeypatch.setattr(_kernels, "on_chip", lambda: True)
    assert ss._kernels_run(64, 128, 64, 256)
    assert ss._kernels_run(64, 128, 8, 128)
    x = jax.ShapeDtypeStruct((1, 2048, 64, 64), jnp.bfloat16)
    bc = jax.ShapeDtypeStruct((1, 2048, 1, 128), jnp.bfloat16)
    assert ss._grid(x, bc, 256) == (8, 8, 128)
    states = ss._states_shape(x, bc, 256)
    assert states.shape == (16, 1, 64, 64, 128)
    assert states.dtype == jnp.float32
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        data = {n: layers.data(name=n, shape=list(s), dtype="float32",
                               append_batch_size=False)
                for n, s in (("x", (1, 512, 64, 64)), ("b", (1, 512, 1, 128)),
                             ("c", (1, 512, 1, 128)), ("dt_raw", (1, 512, 64)))}
        layers.ssd_scan(data["x"], data["b"], data["c"], data["dt_raw"],
                        chunk=256)
    (op,) = [o for o in main.global_block().ops if o.type == "ssd_scan"]
    declared = main.global_block().var(op.outputs["States"][0])
    assert tuple(declared.shape) == (4, 1, 64, 64, 128)
    # off the plan the XLA form's chunk is the attribute's
    tiny = jax.ShapeDtypeStruct((1, 128, 4, 16), jnp.float32)
    assert ss._grid(tiny, jax.ShapeDtypeStruct((1, 128, 1, 16), jnp.float32),
                    64) == (1, 4, 64)


def test_interpreted_kernels_take_a_group_in_blocks_of_heads(monkeypatch):
    """`ssd_fwd` / `ssd_bwd` under the Pallas interpreter at ONE group of 16
    heads (two blocks of 8 that read the same B and C), 512 tokens, chunk
    attribute 256 (four steps of 128 inside), float32, against the XLA form
    at chunk 256 and its `jax.vjp`: the output, the states' shape (one every
    128 tokens, the first zero) and all six gradients. dB and dC are the
    sums over both blocks' parts. Forward
    within RTOL: the kernels' running sums restart every 128 tokens, the
    XLA form's every 256, as between two chunks of the XLA form (first
    reading 1.04e-5 of the largest value)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert ss._plan(64, 128, 16, 256) == "kernel"
    args = _published_scan(seed=5, T=512, heads=16)
    assert ss._grid(args[0], args[3], 256) == (2, 8, 128)
    out, states = ss._ssd_forward(*args, 256)
    want, vjp = jax.vjp(lambda *a: ss.chunked_ssd(*a, 256), *args)
    assert states.shape == (4, 1, 16, 64, 128)
    assert np.all(np.asarray(states[0]) == 0)
    assert rel_err(out, want) < RTOL
    # the same scan at the attribute 128 is the same call
    again, _ = ss._ssd_forward(*args, 128)
    assert np.array_equal(np.asarray(out), np.asarray(again))
    d_out = jnp.asarray(np.random.RandomState(6).randn(*out.shape),
                        jnp.float32)
    got = ss._ssd_backward(*args, states, d_out, 256)
    for name, g, w in zip(ss._SLOTS, got, vjp(d_out)):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert frob(g, w) < 1e-4, \
            f"{name} (B's and C's are the sums over the group's head blocks)"
    # one block's part alone is not the group's gradient
    x, dt, a, b, c, D = args
    half = ss._ssd_backward(x[:, :, :8], dt[:, :, :8], a[:, :, :8], b, c,
                            D[:8], states[:, :, :8], d_out[:, :, :8], 256)
    assert frob(half[3], got[3]) > 0.1 and frob(half[4], got[4]) > 0.1


# -- the gated norm over ONE group as wide as the mixer ------------------------------------------

def _gate_first_want(x, z, w, eps=1e-5):
    u = x * jax.nn.silu(z)
    return u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + eps) * w


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_gated_norm_over_one_group(monkeypatch, kernels):
    """`gated_rms_norm(gate_first=True, group_size=width)`: one mean over all
    the lanes; forward, dX, dGate and dScale against jnp, the XLA form off
    the lane tile and the interpreted kernels on it."""
    if kernels:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    T, width = (32, 512) if kernels else (24, 24)
    assert (db._gated_norm_plan((2, T, 1, width), jnp.dtype("float32"))
            == "kernel") == kernels
    rng = np.random.RandomState(5)
    x = rng.randn(2, T, width).astype(np.float32)
    z = rng.randn(2, T, width).astype(np.float32)
    w = rng.uniform(0.5, 1.5, width).astype(np.float32)
    (y,), grads, probe = run_piece(
        lambda d: [layers.gated_rms_norm(
            d["x"], d["z"], epsilon=1e-5, gate_first=True, group_size=width,
            param_attr=fluid.ParamAttr(name="w"))], {"x": x, "z": z},
        {"w": w})
    assert rel_err(y, _gate_first_want(x, z, w)) < RTOL
    want = jax.grad(lambda *a: jnp.sum(_gate_first_want(*a) * probe),
                    (0, 1, 2))(x, z, w)
    for name, g in zip(("x", "z", "w"), want):
        assert rel_err(grads[name], g) < 1e-4, name


@pytest.mark.parametrize("T,H,D,itemsize,forward,backward", [
    (2048, 1, 4096, 2, (32, 1), (32, 1)),       # Granite 4.0-H: one group
    (2048, 1, 4096, 4, (32, 1), (32, 1)),
    (2048, 8, 512, 2, (128, 8), (256, 4)),      # Nemotron-H, as it was
    (4096, 32, 128, 2, (128, 32), (256, 16)),   # Qwen3-Next, as it was
    (4096, 32, 128, 4, (128, 16), (256, 8))],
    ids=["granite_bf16", "granite_f32", "nemotron", "qwen3_next_bf16",
         "qwen3_next_f32"])
def test_a_wide_head_takes_fewer_rows_a_block(T, H, D, itemsize, forward,
                                              backward):
    """A head of 4096 lanes takes 32 rows a grid step (its float32 tiles fit
    the scoped VMEM: `tests/test_mosaic_compile.py` compiles it); the
    accepted shapes keep their blocks."""
    assert db._gated_norm_blocks(T, H, D, itemsize, False) == forward
    assert db._gated_norm_blocks(T, H, D, itemsize, True) == backward


# -- the model -----------------------------------------------------------------------------------

def _seeded_values(shapes, seed=3):
    """Weights far from their initial values, so that no term of the
    comparison is small by construction: norm weights and D in [0.5, 1.5],
    decays `A_log` in log [1, 8], `dt_bias` around -1, a convolution bias of
    std 0.3, query and key projections of std 0.4 a head times a factor from
    0.5 to 2 over the heads (at the scale 1/64 a softmax over unit scores is
    nearly uniform, and on equal heads the wrong key-value head is as good
    as the right one), the other matrices of std 0.1 (five times the
    initial)."""
    rng = np.random.RandomState(seed)
    values = {}
    for name in sorted(shapes):
        shape = shapes[name]
        if "norm" in name or name.endswith(".D"):
            value = rng.uniform(0.5, 1.5, shape)
        elif name.endswith("A_log"):
            value = np.log(rng.uniform(1, 8, shape))
        elif name.endswith("dt_bias"):
            value = rng.randn(*shape) * 0.5 - 1.0
        elif name.endswith("conv.b"):
            value = rng.randn(*shape) * 0.3
        elif name.endswith("conv.w"):
            value = rng.uniform(-0.5, 0.5, shape)
        elif name.endswith((".attn.q.w", ".attn.k.w", ".attn.v.w")):
            heads = shape[1] // TINY["head_dim"]
            value = (rng.randn(shape[0], heads, TINY["head_dim"]) * 0.4
                     * np.geomspace(0.5, 2.0, heads)[None, :, None]) \
                .reshape(shape)
        else:
            value = rng.randn(*shape) * 0.1
        values[name] = value.astype(np.float32)
    return values


FETCHES = ["loss", "ce", "logits"]


# what each planted fault has to move, at least: the logits or a gradient by
# 1% where the true reference is met within 2e-4
FAULT_WRT = ["embed.w", "l0.mamba.in.w", "l0.mamba.A_log", "l0.mamba.dt_bias",
             "l0.mamba.conv.b", "l0.mamba.norm.w", "l2.mamba.D",
             "l5.attn.q.w", "l5.attn.k.w", "l0.mlp.up.w", "final_norm.w"]
CASE = DecoderCase(models.granite_hybrid.build, TINY, ref, REF_KW, FETCHES,
                   seeded_values=_seeded_values, fault_wrt=FAULT_WRT)


@pytest.fixture(scope="module")
def tiny():
    return CASE.tiny_model()


MAMBA = ["mamba.in.w", "mamba.conv.w", "mamba.conv.b", "mamba.A_log",
         "mamba.dt_bias", "mamba.D", "mamba.norm.w", "mamba.out.w"]
ATTN = ["attn.q.w", "attn.k.w", "attn.v.w", "attn.o.w"]
MLP = ["mlp_norm.w", "mlp.gate.w", "mlp.up.w", "mlp.down.w"]
OF_KIND = {"mamba": MAMBA, "attention": ATTN}
TRAINED = (["embed.w", "final_norm.w"]
           + [f"l{i}.{n}" for i, kind in enumerate(TYPES)
              for n in ["norm.w"] + OF_KIND[kind] + MLP])


def test_tiny_model_has_the_reference_parameters(tiny):
    inner, bc = 4 * 16, 1 * 16
    CASE.has_the_reference_parameters(tiny, TRAINED, {
        "embed.w": (128, 64), "l0.mamba.in.w": (64, 2 * inner + 2 * bc + 4),
        "l0.mamba.conv.w": (inner + 2 * bc, 4),
        "l0.mamba.conv.b": (inner + 2 * bc,), "l2.mamba.A_log": (4,),
        "l2.mamba.dt_bias": (4,), "l2.mamba.D": (4,),
        "l4.mamba.norm.w": (inner,), "l5.attn.q.w": (64, 4 * 16),
        "l5.attn.k.w": (64, 2 * 16), "l5.attn.v.w": (64, 2 * 16),
        "l5.mlp.gate.w": (64, 96), "l0.mlp.up.w": (64, 96),
        "l9.mlp.down.w": (96, 64)})
    assert "head.w" not in tiny["params"]           # tied


def test_the_initial_values_are_the_public_ones():
    main, startup, _, _ = CASE.program()
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    value = lambda n: np.asarray(scope.find_var(n))
    assert np.allclose(value("l0.mamba.A_log"), np.log([1, 2, 3, 4]))
    assert np.all(value("l0.mamba.D") == 1)
    assert np.all(value("l0.mamba.conv.b") == 0)
    assert np.abs(value("l0.mamba.conv.w")).max() <= 0.5
    dt = np.log1p(np.exp(value("l2.mamba.dt_bias")))     # softplus
    assert np.all(dt >= 0.001 * 0.999) and np.all(dt <= 0.1 * 1.001)
    assert not np.array_equal(value("l0.mamba.dt_bias"),
                              value("l2.mamba.dt_bias"))
    # no projection starts smaller than the others (Nemotron-H's do)
    for out, plain in (("l0.mamba.out.w", "l0.mamba.in.w"),
                       ("l5.attn.o.w", "l5.attn.q.w"),
                       ("l0.mlp.down.w", "l0.mlp.up.w"),
                       ("embed.w", "l0.mlp.up.w")):
        ratio = value(plain).std() / value(out).std()
        assert 0.9 < ratio < 1.1, (out, ratio)
    assert all(np.all(value(n) == 1) for n in
               ("l0.norm.w", "l0.mlp_norm.w", "l0.mamba.norm.w",
                "final_norm.w"))


@pytest.mark.parametrize("name", FETCHES)
def test_tiny_model_output_matches_reference(tiny, name):
    CASE.output_matches_reference(tiny, name)


@pytest.mark.parametrize("name", TRAINED)
def test_tiny_model_gradient_matches_reference(tiny, name):
    CASE.gradient_matches_reference(tiny, name)


# -- the tied table ------------------------------------------------------------------------------

def test_the_tied_tables_gradient_is_the_sum_of_the_untied_models_two(tiny):
    """The same weights with the head as a parameter of its own, `head.w` =
    `embed.w`^T: the logits are the tied model's, and the tied table's
    gradient is the untied embedding's (the look-up's row scatter) plus the
    untied head's, transposed (the dense product)."""
    weights = {**tiny["params"], "head.w": tiny["params"]["embed.w"].T.copy()}
    _, params, _, got, grads, _ = CASE.run_tiny(
        amp=False, weights=weights, tie_embeddings=False)
    assert sorted(params) == sorted(TRAINED + ["head.w"])
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


def test_the_table_is_read_twice_and_summed_once(tiny):
    """One parameter, two forward reads (`lookup_table`'s W and `matmul`'s Y),
    a fan-in of two in the backward pass, one Adam op."""
    main, _, _, _ = CASE.program(fluid.optimizer.Adam(learning_rate=1e-3))
    block = main.global_block()
    reads = [op.type for op in block.ops
             if op.attrs.get("__role__") is None
             and "embed.w" in op.input_arg_names]
    assert sorted(reads) == ["lookup_table", "matmul"]
    detail = census.parameter_sharing(main)
    assert detail["grad_fanin_max"] == 2
    assert detail["parameter_uses"] == detail["parameters"] + 1
    updates = [op for op in block.ops if op.type == "adam"
               and op.input("Param") == ["embed.w"]]
    assert len(updates) == 1
    assert not any(p.name == "head.w" for p in block.all_parameters())


def test_a_tied_head_reads_the_table_embed_made():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match="reads embed.w as"):
            tokens = layers.data(name="t", shape=[2, 8], dtype="int64",
                                 append_batch_size=False)
            x = models._decoder.embed(tokens, 32, 16)
            models._decoder.tied_head(x, 64)


# -- the multipliers -----------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["embedding_multiplier",
                                  "residual_multiplier",
                                  "attention_multiplier", "logits_scaling"])
def test_each_multiplier_is_read_from_its_argument(tiny, name):
    """Set to 1 the program is another function, and the reference's with
    the same argument."""
    _, params, _, got, grads, _ = CASE.run_tiny(
        amp=False, weights=tiny["params"], **{name: 1.0})
    moved = rel_err(got["logits"], tiny["got"]["logits"])
    assert moved > 0.01, (name, moved)
    want, want_grads = ref.loss_and_grads(
        params, tiny["tokens"], tiny["labels"], last=TINY["seq_len"],
        wrt=["embed.w", "l5.attn.q.w", "l0.mamba.in.w"],
        **{**REF_KW, name: 1.0})
    assert rel_err(got["logits"], np.asarray(want["logits"])) < 1e-4
    for n, g in want_grads.items():
        assert frob(grads[n], g) < 2e-4, n


def test_the_softmax_scale_is_the_published_number(tiny):
    """`fused_attention(sm_scale=)` takes 1/64 as it is written: not
    head_dim^-0.5 (0.25 at the tiny head of 16, 0.125 as published)."""
    (op,) = [o for o in tiny["main"].global_block().ops
             if o.type == "fused_attention"]
    assert op.attrs["sm_scale"] == 0.015625 != TINY["head_dim"] ** -0.5
    scales = sorted(o.attrs["scale"] for o in tiny["main"].global_block().ops
                    if o.type == "scale" and o.attrs.get("__role__") is None)
    assert scales == [0.125] + [0.22] * 20 + [12.0]


# -- the planted faults --------------------------------------------------------------------------

@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_each_planted_fault_is_refused(tiny, fault):
    """(`untied_head` moves no forward number: the table's gradient
    alone.)"""
    CASE.planted_fault_is_refused(tiny, fault)


def test_the_config_names_every_fault_and_no_other():
    assert sorted(CONFIG["reference"]["check"]["faults"]) == sorted(ref.FAULTS)
    assert len(ref.FAULTS) == 16


def test_an_unknown_fault_is_refused(tiny):
    CASE.unknown_fault_is_refused(tiny)


def test_reference_in_blocks_is_the_reference(tiny):
    CASE.reference_in_blocks_is_the_reference(
        tiny, ["l0.mamba.in.w", "l2.mamba.A_log", "l5.attn.k.w",
               "l3.mlp.down.w", "embed.w"], q_block=32, token_block=16)


def test_reference_last_positions_equal_the_full_pass(tiny):
    CASE.reference_last_positions_equal_the_full_pass(tiny)


def test_reference_in_bfloat16_is_another_number(tiny):
    CASE.reference_in_bfloat16_is_another_number(tiny)


# -- AMP -----------------------------------------------------------------------------------------

def test_tiny_model_amp_within_bf16_of_reference():
    """Under AMP the projections, the scan's x, B and C, attention, the
    feed-forwards and the head's product (the table cast once) are bf16; the
    embedding's rows and their multiplier, dt, a, the scan's sums and state
    and every norm's statistics stay float32. At the initial weights. A bf16
    value carries 8 bits: logits of std ~0.02 here read within 0.002 in the
    mean, the loss within 0.002, a gradient within 5% in the Frobenius norm,
    the decay's and step size's (a few numbers downstream of every rounding)
    within 15%."""
    CASE.amp_within_bf16_of_reference(
        {0.05: ("l0.mamba.in.w", "l0.mamba.out.w", "l5.attn.k.w",
                "l0.mlp.up.w", "embed.w", "final_norm.w"),
         0.15: ("l0.mamba.A_log", "l0.mamba.dt_bias", "l0.mamba.conv.b")},
        mean=0.002, most=0.02, of_std=False)


def test_amp_lists_say_what_reads_the_table_in_which_precision():
    """The head's product is on the bf16 list (it casts the table once);
    the look-up and the multipliers are on no list, so the embedding's rows
    and `12 *` them stay float32 and a bf16 branch stays bf16 under its
    0.22; the gates are float32."""
    assert "matmul" in registry.AMP_BF16_OPS
    assert "ssd_gates" in registry.AMP_F32_OPS
    for op in ("lookup_table", "scale", "ssd_scan", "causal_conv1d",
               "gated_rms_norm", "rms_norm", "swiglu"):
        assert op not in registry.AMP_F32_OPS | registry.AMP_BF16_OPS


def test_five_adam_steps_lower_the_loss():
    CASE.adam_steps_lower_the_loss()


def test_layer_types_is_a_list_over_mamba_and_attention():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match="layer_types holds"):
            models.granite_hybrid.build(
                **{**TINY, "layer_types": ["mamba", "moe"]})
    assert list(models.granite_hybrid.GRANITE_4_0_H) \
        == CONFIG["layer_types"] == list(ref.LAYER_TYPES)


# -- what the Program holds; spans and counters --------------------------------------------------

@pytest.mark.parametrize("layer", range(10))
def test_every_layer_is_a_mixer_and_a_feed_forward_under_their_scopes(
        tiny, layer):
    """A layer holds ONE of a scan and an attention op under its mixer's
    scope and a gated feed-forward under `l<i>.mlp`, each with its own norm,
    its scale and its residual add inside, and no rotary op anywhere."""
    scopes = _forward_ops_by_scope(tiny["main"])
    kind = TYPES[layer]
    assert [f"l{layer}.{k}" in scopes for k in ("mamba", "attn")] \
        == [kind == "mamba", kind == "attention"]
    ops = scopes[f"l{layer}." + models.granite_hybrid.KINDS[kind]]
    for name in ("ssd_scan", "ssd_gates", "causal_conv1d", "gated_rms_norm"):
        assert ops.count(name) == (kind == "mamba"), name
    assert ops.count("fused_attention") == (kind == "attention")
    mlp = scopes[f"l{layer}.mlp"]
    assert mlp.count("swiglu") == 1 and mlp.count("mul") == 3
    for sub in (ops, mlp):
        assert (sub.count("rms_norm"), sub.count("scale"),
                sub.count("elementwise_add")) == (1, 1, 1)
        assert "rotary_embedding" not in sub
    # outside every scope: the look-up and its multiplier, the final norm,
    # the tied head and its scale, the loss
    assert scopes[None] == ["lookup_table", "scale", "rms_norm", "matmul",
                            "scale", "softmax_with_cross_entropy", "mean"]


CENSUS = {"layer_kinds": {"state_space": 9, "full_attention": 1},
          "state_space_layers": 9, "state_space_groups": 1,
          "state_space_heads_per_group": 64, "state_space_chunk": 256,
          "attention_unrotated_layers": 1, "attention_kv_group": 4,
          "tied_heads": 1, "residual_scaled_sublayers": 20}
CENSUS_SIZES = dict(mamba_heads=64, mamba_head_dim=2, n_head=32, n_kv_head=8,
                    head_dim=4, seq_len=256, chunk=256)


def test_layer_census_reads_the_issues_counts():
    """9 state-space layers of one group of 64 heads at chunk 256, 1
    full-attention layer with no rotary at a key-value group of 4, one tied
    head, 20 sublayers under a residual multiplier."""
    main, _, _, _ = CASE.program(fluid.optimizer.SGD(learning_rate=1e-3),
                             **CENSUS_SIZES)
    got = census.layer_census(main)
    assert got == CENSUS
    assert "attention_rotary_layers" not in got
    assert "dense_ffn_layers" not in got        # that key goes with routers
    # untied, and with every multiplier written as 1, the program says so
    main, _, _, _ = CASE.program(tie_embeddings=False)
    assert "tied_heads" not in census.layer_census(main)


@pytest.fixture(scope="module")
def compile_detail():
    return CASE.compile_detail()


@pytest.mark.parametrize("key,value", [
    ("state_space_layers", 9), ("attention_unrotated_layers", 1),
    ("attention_kv_group", 2), ("state_space_groups", 1),
    ("state_space_heads_per_group", 4), ("state_space_chunk", 64),
    ("ssd_plan", "xla"), ("tied_heads", 1),
    ("residual_scaled_sublayers", 20), ("grad_fanin_max", 2)])
def test_compile_event_carries_the_census(compile_detail, key, value):
    carries_the_census(compile_detail, {key: value},
                       absent=["ssd_grid_steps"])   # tallied where kernels run


@pytest.mark.parametrize("model,keys", [
    ("nemotron_h", {"state_space_groups": 2, "state_space_heads_per_group": 2,
                    "state_space_chunk": 128}),
    ("olmo_hybrid", {}), ("trinity", {}), ("ouro", {})])
def test_the_new_keys_go_with_what_they_count(model, keys):
    """Nemotron-H's scans gain their three keys; no program without a scan,
    a tied table or a scaled branch gains any (Trinity scales its embedding
    outside every scope, Ouro shares every weight but ties no head)."""
    got = census.layer_census(build_program(model)[0])
    new = ("state_space_groups", "state_space_heads_per_group",
           "state_space_chunk", "tied_heads", "residual_scaled_sublayers")
    assert {k: got[k] for k in new if k in got} == keys


# -- the copies and the harness ------------------------------------------------------------------

def test_the_two_copies_of_the_reference_are_identical():
    CASE.two_copies_of_the_reference_are_identical()


def test_the_config_holds_the_published_widths_and_the_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = []
    if os.path.exists(catalog):     # the builder's machine has it
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
    want = {"hidden_size": 2048, "intermediate_size": 8192,
            "shared_intermediate_size": 8192, "mamba_n_heads": 64,
            "mamba_d_head": 64, "mamba_n_groups": 1, "mamba_d_state": 128,
            "mamba_d_conv": 4, "mamba_chunk_size": 256, "mamba_expand": 2,
            "mamba_conv_bias": True, "num_attention_heads": 32,
            "num_key_value_heads": 8, "attention_multiplier": 0.015625,
            "embedding_multiplier": 12, "residual_multiplier": 0.22,
            "logits_scaling": 8, "tie_word_embeddings": True,
            "position_embedding_type": "nope", "num_local_experts": 0,
            "num_hidden_layers": 10, "vocab_size": 12544,
            "num_hidden_layers_published": 40,
            "vocab_size_published": 100352}
    assert {k: CONFIG[k] for k in want} == want
    for row in rows:        # the catalog's row, key for key but the two cut
        if row["name"] == "granite-4.0-h-micro":
            assert CONFIG["source"] == row["source_url"]
            differs = sorted(k for k, v in row["config"].items()
                             if CONFIG.get(k) != v)
            assert differs == ["num_hidden_layers", "vocab_size"]
    assert len(CONFIG["layer_types"]) == 40
    assert [i for i, k in enumerate(CONFIG["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert [r.split()[0] for r in CONFIG["reduced"]] == [
        "num_hidden_layers", "vocab_size"]
    assert "layer_types" in CONFIG["reduced"][0]
    args = CONFIG["build_args"]
    assert args["layer_types"] == CONFIG["layer_types"][:10] == TYPES
    assert (args["d_model"], args["d_ff"], args["mamba_heads"],
            args["mamba_head_dim"], args["n_groups"], args["ssm_state"],
            args["chunk"], args["n_head"], args["n_kv_head"],
            args["head_dim"], args["vocab_size"]) == \
        (2048, 8192, 64, 64, 1, 128, 256, 32, 8, 64, 12544)
    assert "four pipeline stages" in CONFIG["deployment"]
    assert str(CONFIG["parameters"]) in CONFIG["deployment"].replace(",", "")
    assert CONFIG["parameter_bytes"]["that_stay"] == 12 * CONFIG["parameters"]


def test_the_parameter_count_is_the_programs():
    """The configuration's `parameters`, the FLOP module's count and the
    Program's own, at the published widths (nothing runs: shapes alone)."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from flops import granite_hybrid as counts
    finally:
        sys.path.pop(0)
    args = {**CONFIG["build_args"], "seq_len": 2048}
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        models.granite_hybrid.build(**args)
    held = sum(int(np.prod(p.shape))
               for p in main.global_block().all_parameters())
    flops = counts.flops_per_example(**args)
    assert held == flops["parameters"] == CONFIG["parameters"] == 772160448
    assert abs(flops["forward_backward"] / 1e12 - 9.774) < 1e-3
    assert abs(flops["mlp_share"] - 0.633) < 1e-3
    assert abs(flops["mamba_scans_share"] - 0.0241) < 1e-4


def test_the_tiny_block_runs_through_the_benchmark():
    runs_through_the_benchmark("granite_4_0_h_micro.s2048")
