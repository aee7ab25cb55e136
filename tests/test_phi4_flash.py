"""Phi-4-mini-flash (the model's own layers 14-19: Mamba-1 mixers through
`selective_scan`, differential attention under a window and full, a gated
memory unit on layer 16's scan output, cross attention on layer 17's keys and
values, LayerNorm, a gated MLP in every layer, one table as embedding and
head) through `layers` -> Program IR -> `Executor`, against the plain
reference (`tests/phi4_flash_reference.py`: the recurrence token by token, a
convolution of shifted products plus its bias, each softmax map a masked
softmax over the whole row, `jnp.repeat`, the table used twice). The sizes
are the configuration's `tiny` block; the `tiny` fixture runs under the
Pallas interpreter, so the Program's scans are `sscan_fwd` / `sscan_bwd`
(128 channels are one lane tile, 128 tokens one chunk). Seeded random
weights, float32, AMP off unless a test says otherwise.

Tolerances: a float32 program against a float32 reference at "highest" agrees
to a few 1e-6 in a product's result; through six layers of two sublayers and
five softmaxes the logits stay within 1e-4 of their largest value and a
gradient within 2e-4 in the Frobenius norm (`test_granite_hybrid.py`'s
limits, for its reason). The scan's forms against the recurrence sum the same
products in another order (an associative scan inside a chunk; the kernels
token by token like the recurrence): 2e-5 of the largest value (RTOL), a
gradient within 1e-4."""

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
from paddle_tpu.ops import selective_scan as sscan

import phi4_flash_reference as ref
from decoder_case import (ROOT, DecoderCase, _forward_ops_by_scope,
                          build_program, carries_the_census, config, frob,
                          piece_noted, rel_err, run_piece,
                          runs_through_the_benchmark, tiny_args)

CONFIG = config("phi4_flash")
# the model's own layers 14-19 of 32, hidden 64, MLPs of 96, Mamba-1 at 128
# channels x 8 states and a dt rank of 4, 8 / 4 heads of 8 (4 query pairs
# over 2 key-value pairs), a window of 48 (no multiple of a tile), 128 tokens
TINY = tiny_args("phi4_flash")
REF_KW = {k: TINY[k] for k in (
    "n_layer", "mb_per_layer", "window", "first_layer", "layers_held",
    "n_head", "n_kv_head", "head_dim", "norm_eps", "chunk")}
HELD = range(14, 20)
KINDS = ["mamba", "window", "mamba", "full", "gmu", "cross"]
RTOL = 2e-5


def test_the_tiny_block_is_the_issues():
    assert (TINY["n_layer"], TINY["mb_per_layer"], TINY["first_layer"],
            TINY["layers_held"]) == (32, 2, 14, 6)
    assert (TINY["seq_len"], TINY["window"], TINY["chunk"], TINY["d_model"],
            TINY["d_ff"]) == (128, 48, 32, 64, 96)
    assert (TINY["n_head"], TINY["n_kv_head"], TINY["head_dim"],
            TINY["ssm_state"], TINY["dt_rank"]) == (8, 4, 8, 8, 4)
    # every size that sets the cost is overridden; what stays is no size
    kept = set(CONFIG["build_args"]) - set(CONFIG["tiny"]["build_args"])
    assert kept == {"n_layer", "mb_per_layer", "first_layer", "layers_held",
                    "conv_kernel", "expand", "time_step", "norm_eps"}


# -- the published rule --------------------------------------------------------------------------

def test_the_32_layers_are_the_published_kinds():
    """8 Mamba layers, 8 window layers, the memory-giving Mamba layer, the
    one full layer, 7 gated memory units, 7 cross layers: program, reference
    and the benchmark's FLOP count alike."""
    kinds = [models.phi4_flash.layer_kind(l) for l in range(32)]
    assert kinds == [ref.layer_kind(l) for l in range(32)]
    assert kinds[14:20] == KINDS
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert [l for l, k in enumerate(kinds) if k == "mamba"] \
        == list(range(0, 18, 2))
    assert [l for l, k in enumerate(kinds) if k == "window"] \
        == list(range(1, 16, 2))
    assert kinds[16] == "mamba" and kinds[17] == "full"
    assert [l for l, k in enumerate(kinds) if k == "gmu"] \
        == list(range(18, 32, 2))
    assert [l for l, k in enumerate(kinds) if k == "cross"] \
        == list(range(19, 32, 2))
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        models.phi4_flash.build(**{**TINY, "first_layer": 0,
                                   "layers_held": None})
    got = census.layer_census(main)
    assert got["layer_kinds"] == {
        "selective_scan": 9, "differential_attention": 9,
        "cross_decoder_attention": 7, "gated_memory": 7}
    assert (got["attention_window_layers"], got["shared_kv_readers"],
            got["memory_readers"]) == (8, 7, 7)
    # layer 17's served k is read by its own call and seven cross layers',
    # its v by both calls of each: 8 and 16; layer 16's y by its gate and
    # seven gated memory units
    main2 = fluid.Program()
    with fluid.program_guard(main2, fluid.Program()), \
            fluid.unique_name.guard():
        _, fetches = models.phi4_flash.build(
            **{**TINY, "first_layer": 0, "layers_held": None})
        fluid.append_backward(fetches["loss"])
    assert census.layer_census(main2)["activation_grad_fanin_max"] == 16


@pytest.mark.parametrize("first,held,missing", [
    (18, 2, 16), (19, 1, 17), (17, 3, 16), (18, 14, 16), (20, 4, 16)],
    ids=["gmu_and_cross", "cross_alone", "without_16", "second_half",
         "a_later_stage"])
def test_a_held_run_without_its_memory_raises(first, held, missing):
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match=f"layer {missing} keeps"):
            models.phi4_flash.build(**{**TINY, "first_layer": first,
                                       "layers_held": held})


@pytest.mark.parametrize("first,held", [(0, 7), (7, 7), (14, 6), (16, 16)],
                         ids=["stage_0", "stage_1", "stage_2", "from_16"])
def test_a_held_run_with_its_memory_builds(first, held):
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        models.phi4_flash.build(**{**TINY, "first_layer": first,
                                   "layers_held": held})
    names = {p.name.split(".")[0] for p in main.global_block().all_parameters()}
    assert names == {f"l{l}" for l in range(first, first + held)} \
        | {"embed", "final_norm"}


def test_a_held_run_outside_the_model_raises():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match="held layers lie in"):
            models.phi4_flash.build(**{**TINY, "first_layer": 30,
                                       "layers_held": 4})


def test_lambda_init_follows_the_published_index(tiny):
    """The `(1 - lam0)` scales and lam's offsets in the Program are those of
    layers 15, 17 and 19, not of layers 1, 3, 5 of the held run."""
    ops = [o for o in tiny["main"].global_block().ops
           if o.type == "scale" and o.attrs.get("__role__") is None]
    for l in (15, 17, 19):
        lam0 = 0.8 - 0.6 * np.exp(-0.3 * l)
        mine = [o for o in ops if o.attrs[fluid.core.ir.NAME_SCOPE_ATTR]
                .startswith(f"l{l}.")]
        assert sorted((round(o.attrs["scale"], 6), round(o.attrs["bias"], 6))
                      for o in mine) \
            == sorted([(1.0, round(lam0, 6)), (round(1 - lam0, 6), 0.0)])


# -- the selective scan --------------------------------------------------------------------------

SCAN_NAMES = ["x", "dt_raw", "b", "c", "A_log", "dt_bias", "D"]


def _scan_inputs(B, T, channels, N, seed=0):
    rng = np.random.RandomState(seed)
    f = np.float32
    return ({"x": rng.randn(B, T, channels).astype(f),
             "dt_raw": rng.randn(B, T, channels).astype(f),
             "b": rng.randn(B, T, N).astype(f) * 0.5,
             "c": rng.randn(B, T, N).astype(f) * 0.5},
            {"A_log": np.log(rng.uniform(0.5, 8, (channels, N))).astype(f),
             "dt_bias": (rng.randn(channels) * 0.5 - 1.0).astype(f),
             "D": rng.uniform(0.5, 1.5, channels).astype(f)})


def _recurrence(x, dt_raw, b, c, A_log, dt_bias, D):
    return ref.selective_scan(x, jax.nn.softplus(dt_raw + dt_bias),
                              -jnp.exp(A_log), b, c, D)


def _scan_layer(chunk=128):
    def build(d):
        return [layers.selective_scan(
            d["x"], d["dt_raw"], d["b"], d["c"], d["b"].shape[-1],
            chunk=chunk, a_log_attr=fluid.ParamAttr(name="A_log"),
            dt_bias_attr=fluid.ParamAttr(name="dt_bias"),
            d_attr=fluid.ParamAttr(name="D"))]
    return build


def _against_the_recurrence(feed, params, chunk=128):
    (y,), grads, probe = run_piece(_scan_layer(chunk), feed, params)
    args = [jnp.asarray({**feed, **params}[n]) for n in SCAN_NAMES]
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*args)
        want_grads = jax.grad(
            lambda *a: jnp.sum(_recurrence(*a) * probe),
            range(len(args)))(*args)
    assert rel_err(y, want) < RTOL
    for name, g in zip(SCAN_NAMES, want_grads):
        assert frob(grads[name], g) < 1e-4, name
    return y, grads


@pytest.mark.parametrize("B,T,channels,N,chunk", [
    (2, 64, 24, 16, 16), (1, 96, 40, 4, 32), (1, 60, 8, 16, 128)],
    ids=["four_chunks", "three_chunks_four_states", "a_chunk_past_the_end"])
def test_the_plain_form_is_the_recurrence(B, T, channels, N, chunk):
    """The op off the plan (channels off the lane tile): `scan_plain` and the
    vjp of its checkpointed chunks against the token-by-token recurrence,
    forward and all seven gradients."""
    assert sscan._plan(T, channels, N) == "plain"
    feed, params = _scan_inputs(B, T, channels, N, seed=T)
    _against_the_recurrence(feed, params, chunk)
    assert piece_noted("selective_scan_plan") == "plain"
    assert piece_noted("selective_scan_grid_steps") is None


@pytest.mark.parametrize("B,T,channels,N", [
    (1, 128, 128, 8), (2, 256, 128, 16), (1, 128, 1024, 16)],
    ids=["one_step", "two_chunks_two_sequences", "two_blocks_of_512"])
def test_interpreted_kernels_are_the_recurrence(monkeypatch, B, T, channels,
                                                N):
    """The op on the plan under the Pallas interpreter: `sscan_fwd` saves a
    state a chunk and `sscan_bwd` alone makes all seven gradients from them;
    against the recurrence, and the grid steps tallied forward and
    backward."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert sscan._kernels_run(T, channels, N)
    feed, params = _scan_inputs(B, T, channels, N, seed=channels)
    _against_the_recurrence(feed, params)
    assert piece_noted("selective_scan_plan") == "kernel"
    blocks = channels // min(512, channels)
    assert piece_noted("selective_scan_grid_steps") \
        == 2 * B * (T // 128) * blocks


def test_interpreted_kernels_are_the_plain_form(monkeypatch):
    """The two forms on the same float32 inputs: y, the saved states' shape
    (the first zero) and the six gradients of `_sscan_backward`; a narrower
    channel block is the same call."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    feed, params = _scan_inputs(1, 256, 256, 16, seed=9)
    v = {k: jnp.asarray(a) for k, a in {**feed, **params}.items()}
    dt, A = sscan.gates(v["dt_raw"], v["dt_bias"], v["A_log"])
    args = (v["x"], dt, A, v["b"], v["c"], v["D"])
    y, states = sscan._sscan_forward(*args)
    want, vjp = jax.vjp(lambda *a: sscan.scan_plain(*a, chunk=64), *args)
    assert states.shape == (2, 1, 16, 256) and states.dtype == jnp.float32
    assert np.all(np.asarray(states[0]) == 0)
    assert rel_err(y, want) < RTOL
    d_out = jnp.asarray(np.random.RandomState(6).randn(*y.shape), jnp.float32)
    got = sscan._sscan_backward(*args, states, d_out)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), got, vjp(d_out)):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert frob(g, w) < 1e-4, name
    y_128, states_128 = sscan._sscan_forward(*args, widest=128)
    assert np.array_equal(np.asarray(y), np.asarray(y_128))
    assert np.array_equal(np.asarray(states), np.asarray(states_128))


def test_a_state_is_its_own_decay():
    """Sixteen decays a channel: with every state's decay made the first
    state's (one decay a channel, the dual form's rule) y is another
    number, and with `A_log` constant over the states it is not."""
    feed, params = _scan_inputs(1, 64, 24, 16, seed=3)
    (y,), _, _ = run_piece(_scan_layer(16), feed, params)
    shared = {**params, "A_log": np.repeat(params["A_log"][:, :1], 16, 1)}
    (other,), _, _ = run_piece(_scan_layer(16), feed, shared)
    assert rel_err(other, y) > 0.01
    args = [jnp.asarray({**feed, **shared}[n]) for n in SCAN_NAMES]
    assert rel_err(other, _recurrence(*args)) < RTOL


def test_the_recurrence_does_not_depend_on_the_chunk():
    feed, params = _scan_inputs(1, 96, 24, 8, seed=1)
    runs = [run_piece(_scan_layer(chunk), feed, params)
            for chunk in (8, 32, 96)]
    for other in runs[1:]:
        assert rel_err(runs[0][0][0], other[0][0]) < RTOL
        for name in SCAN_NAMES:
            assert frob(runs[0][1][name], other[1][name]) < 1e-4, name


@pytest.mark.parametrize("T,channels,N,plan", [
    (4096, 5120, 16, "kernel"),     # the cell
    (128, 128, 8, "kernel"),        # the tiny block
    (4096, 5120, 4, "plain"),       # a state that is no sublane tile
    (4000, 5120, 16, "plain"),      # no whole chunks of 128 tokens
    (4096, 5000, 16, "plain"),      # channels off the lane tile
    (64, 24, 16, "plain")],
    ids=["cell", "tiny", "four_states", "ragged_tokens", "ragged_channels",
         "small"])
def test_the_plan_reads_the_shape_alone(T, channels, N, plan):
    assert sscan._plan(T, channels, N) == plan


def test_the_published_scan_takes_the_kernels(monkeypatch):
    """At the cell's shape on a chip the op takes the kernel pair on a grid
    of 32 chunks x 10 blocks of 512 channels, and a Program built on a
    machine without a TPU declares `States` as the chip's kernels write it:
    a state every 128 tokens, `[T / 128, B, N, channels]` (10.5 MB a layer,
    not the 1.34 GB of `[T, channels, N]`)."""
    from paddle_tpu.ops import _kernels
    assert not sscan._kernels_run(4096, 5120, 16)   # a CPU, no interpreter
    monkeypatch.setattr(_kernels, "on_chip", lambda: True)
    assert sscan._kernels_run(4096, 5120, 16)
    x = jax.ShapeDtypeStruct((1, 4096, 5120), jnp.float32)
    assert sscan._grid(x) == (32, 10, 512)
    assert sscan._block(5120, 256) == 256 and sscan._block(384) == 128
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        d = {n: layers.data(name=n, shape=list(s), dtype="float32",
                            append_batch_size=False)
             for n, s in (("x", (1, 4096, 5120)), ("dt", (1, 4096, 5120)),
                          ("b", (1, 4096, 16)), ("c", (1, 4096, 16)))}
        out = layers.selective_scan(d["x"], d["dt"], d["b"], d["c"], 16)
    (op,) = [o for o in main.global_block().ops
             if o.type == "selective_scan"]
    states = main.global_block().var(op.outputs["States"][0])
    assert tuple(states.shape) == (32, 1, 16, 5120)
    assert int(np.prod(states.shape)) * 4 == 10485760
    assert tuple(out.shape) == (1, 4096, 5120) and out.dtype == "float32"
    params = {p.name: tuple(p.shape)
              for p in main.global_block().all_parameters()}
    assert sorted(params.values()) == [(5120,), (5120,), (5120, 16)]


def test_the_scan_refuses_shapes_that_do_not_belong_together():
    feed, params = _scan_inputs(1, 32, 8, 4)
    feed["c"] = feed["c"][:, :, :2]
    with pytest.raises(Exception, match="selective_scan takes"):
        run_piece(_scan_layer(16), feed, params)


# -- the pieces of a differential layer ----------------------------------------------------------

def test_the_pairs_halves_are_heads_2j_and_2j_plus_1():
    """`_halves` on `[B, T, pairs * 2 * Dh]`: the first of each pair's heads
    and the second, heads first."""
    x = np.arange(2 * 3 * 4 * 2 * 5, dtype=np.float32).reshape(2, 3, 40)
    (first, second), _, _ = run_piece(
        lambda d: models.phi4_flash._halves(d["x"], 4, 5), {"x": x})
    heads = x.reshape(2, 3, 8, 5).transpose(0, 2, 1, 3)
    assert np.array_equal(first, heads[:, 0::2])
    assert np.array_equal(second, heads[:, 1::2])


# -- the model -----------------------------------------------------------------------------------

def _seeded_values(shapes, seed=3):
    """Weights far from their initial values, so that no term of the
    comparison is small by construction: norm weights and D in [0.5, 1.5],
    norm and projection biases of std 0.1, `A_log` in log [1, 8] a channel
    AND state, `dt.b` around -1, a convolution bias of std 0.3, lambda
    vectors of std 0.3 (lam moves by tenths), query, key and value
    projections of std 0.4 a head times a factor from 0.5 to 2 over the heads
    (on equal heads a wrong pairing is as good as the right one), the other
    matrices of std 0.1 (five times the initial)."""
    rng = np.random.RandomState(seed)
    values = {}
    for name in sorted(shapes):
        shape = shapes[name]
        last = name.rsplit(".", 1)[-1]
        if name.endswith((".q.w", ".k.w", ".v.w")):
            heads = shape[1] // TINY["head_dim"]
            value = (rng.randn(shape[0], heads, TINY["head_dim"]) * 0.4
                     * np.geomspace(0.5, 2.0, heads)[None, :, None]) \
                .reshape(shape)
        elif name.endswith("conv.w"):
            value = rng.uniform(-0.5, 0.5, shape)
        elif name.endswith("conv.b"):
            value = rng.randn(*shape) * 0.3
        elif name.endswith("dt.b"):
            value = rng.randn(*shape) * 0.5 - 1.0
        elif last == "A_log":
            value = np.log(rng.uniform(1, 8, shape))
        elif last == "D" or (last == "w" and "norm" in name) \
                or name.endswith("subln.w"):
            value = rng.uniform(0.5, 1.5, shape)
        elif last in ("lq1", "lk1", "lq2", "lk2"):
            value = rng.randn(*shape) * 0.3
        elif last == "b":
            value = rng.randn(*shape) * 0.1
        else:
            value = rng.randn(*shape) * 0.1
        values[name] = value.astype(np.float32)
    return values


FETCHES = ["loss", "ce", "logits"]
# what each planted fault has to move, at least: the logits or a gradient by
# 1% where the true reference is met within 2e-4
FAULT_WRT = ["embed.w", "l14.mamba.in.w", "l14.mamba.A_log", "l14.mamba.dt.b",
             "l14.mamba.conv.b", "l14.mamba.D", "l16.mamba.x.w",
             "l15.attn.q.w", "l15.attn.k.w", "l17.attn.k.w", "l17.attn.v.w",
             "l17.attn.lq1", "l18.gmu.in.w", "l19.cross.q.w",
             "l14.mlp.up.w", "l14.norm.b", "final_norm.w"]
CASE = DecoderCase(models.phi4_flash.build, TINY, ref, REF_KW, FETCHES,
                   seeded_values=_seeded_values, fault_wrt=FAULT_WRT,
                   interpreted=True)


@pytest.fixture(scope="module")
def tiny():
    return CASE.tiny_model()


MAMBA = ["mamba.in.w", "mamba.conv.w", "mamba.conv.b", "mamba.x.w",
         "mamba.dt.w", "mamba.dt.b", "mamba.A_log", "mamba.D", "mamba.out.w"]
LAMBDAS = ["lq1", "lk1", "lq2", "lk2", "subln.w"]
ATTN = [f"attn.{n}" for n in ["q.w", "q.b", "k.w", "k.b", "v.w", "v.b", "o.w",
                              "o.b"] + LAMBDAS]
CROSS = [f"cross.{n}" for n in ["q.w", "q.b", "o.w", "o.b"] + LAMBDAS]
MLP = ["mlp_norm.w", "mlp_norm.b", "mlp.gate.w", "mlp.up.w", "mlp.down.w"]
OF_KIND = {"mamba": MAMBA, "window": ATTN, "full": ATTN,
           "gmu": ["gmu.in.w", "gmu.out.w"], "cross": CROSS}
TRAINED = (["embed.w", "final_norm.w", "final_norm.b"]
           + [f"l{l}.{n}" for l, kind in zip(HELD, KINDS)
              for n in ["norm.w", "norm.b"] + OF_KIND[kind] + MLP])


def test_tiny_model_has_the_reference_parameters(tiny):
    CASE.has_the_reference_parameters(tiny, TRAINED, {
        "embed.w": (128, 64), "l14.mamba.in.w": (64, 256),
        "l14.mamba.conv.w": (128, 4), "l14.mamba.conv.b": (128,),
        "l14.mamba.x.w": (128, 4 + 2 * 8), "l16.mamba.dt.w": (4, 128),
        "l16.mamba.dt.b": (128,), "l16.mamba.A_log": (128, 8),
        "l16.mamba.D": (128,), "l16.mamba.out.w": (128, 64),
        "l15.attn.q.w": (64, 64), "l15.attn.k.w": (64, 32),
        "l17.attn.v.b": (32,), "l17.attn.lq1": (8,),
        "l17.attn.subln.w": (16,), "l18.gmu.in.w": (64, 128),
        "l18.gmu.out.w": (128, 64), "l19.cross.q.b": (64,),
        "l19.cross.o.w": (64, 64), "l19.mlp.gate.w": (64, 96),
        "final_norm.b": (64,)})
    assert not any(".cross.k." in n or ".cross.v." in n
                   for n in tiny["params"])
    assert "head.w" not in tiny["params"]           # tied


def test_the_initial_values_are_the_public_ones():
    main, startup, _, _ = CASE.program()
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    value = lambda n: np.asarray(scope.find_var(n))
    assert np.allclose(value("l14.mamba.A_log"),
                       np.tile(np.log(np.arange(1, 9)), (128, 1)))
    assert np.all(value("l14.mamba.D") == 1)
    assert np.all(value("l14.mamba.conv.b") == 0)
    assert np.abs(value("l14.mamba.conv.w")).max() <= 0.5
    assert np.abs(value("l14.mamba.dt.w")).max() <= 4 ** -0.5
    assert value("l14.mamba.dt.w").std() > 0.2
    dt = np.log1p(np.exp(value("l16.mamba.dt.b")))      # softplus
    assert np.all(dt >= 0.001 * 0.999) and np.all(dt <= 0.1 * 1.001)
    assert not np.array_equal(value("l14.mamba.dt.b"),
                              value("l16.mamba.dt.b"))
    for bias in ("l15.attn.q.b", "l17.attn.k.b", "l17.attn.o.b",
                 "l19.cross.q.b", "l14.norm.b", "final_norm.b"):
        assert np.all(value(bias) == 0), bias
    for name in ("l15.attn.lq1", "l19.cross.lk2"):
        assert 0.03 < value(name).std() < 0.2, name
    for name in ("l14.norm.w", "l19.mlp_norm.w", "l15.attn.subln.w",
                 "final_norm.w"):
        assert np.all(value(name) == 1), name
    for name in ("l14.mamba.in.w", "l15.attn.q.w", "l18.gmu.out.w",
                 "l19.cross.o.w", "l14.mlp.down.w", "embed.w"):
        assert 0.015 < value(name).std() < 0.025, name


@pytest.mark.parametrize("name", FETCHES)
def test_tiny_model_output_matches_reference(tiny, name):
    CASE.output_matches_reference(tiny, name)


# a key bias moves every score of a row alike (q . b), which a softmax does
# not see: its gradient is 0 but for rounding, on both sides
KEY_BIASES = ["l15.attn.k.b", "l17.attn.k.b"]


# lam's gradient is one scalar, `<the pair norm's vjp, a2>`: the norm's vjp
# takes the component along `a1 - lam a2` out, and under the tiny window of 48
# keys the two maps are close to parallel, so the sum cancels to a hundredth
# of its terms and float32 keeps three digits of it (read 1.7e-3 on all four
# vectors alike: the scalar's error; the full layers read 1e-5 and 1e-4)
WINDOW_LAMBDAS = [f"l15.attn.{n}" for n in ("lq1", "lk1", "lq2", "lk2")]


@pytest.mark.parametrize("name", [n for n in TRAINED if n not in KEY_BIASES
                                  + WINDOW_LAMBDAS])
def test_tiny_model_gradient_matches_reference(tiny, name):
    CASE.gradient_matches_reference(tiny, name)


@pytest.mark.parametrize("name", WINDOW_LAMBDAS)
def test_the_window_layers_lambda_gradient_matches_reference(tiny, name):
    assert frob(tiny["grads"][name], tiny["want_grads"][name]) < 5e-3


@pytest.mark.parametrize("name", KEY_BIASES)
def test_a_key_bias_has_no_gradient_but_rounding(tiny, name):
    other = np.linalg.norm(tiny["grads"][name.replace(".k.b", ".v.b")])
    for side in (tiny["grads"], tiny["want_grads"]):
        assert np.linalg.norm(side[name]) < 1e-5 * other


def test_the_plain_form_gives_the_model_the_same_step(tiny):
    """Without the interpreter the Program's scans run `scan_plain` and
    their grad ops its vjp: the same loss, logits and gradients as the
    fixture's kernels."""
    run = CASE.run_tiny(amp=False)
    assert rel_err(run.got["logits"], tiny["got"]["logits"]) < 1e-5
    for name in ["l14.mamba.A_log", "l14.mamba.dt.b", "l14.mamba.x.w",
                 "l16.mamba.D", "l16.mamba.in.w", "l18.gmu.in.w", "embed.w"]:
        assert frob(run.grads[name], tiny["grads"][name]) < 1e-4, name


# -- what later layers read ----------------------------------------------------------------------

def test_the_kept_tensors_gradients_are_sums_over_their_readers(tiny):
    """Layer 17's served k (even and odd heads apart) is read by its own
    flash call and layer 19's, its served v by both calls of both layers,
    layer 16's scan output by its gate and layer 18's: the backward pass
    sums 2, 2, 4 and 2 contributions, and the table's two."""
    block = tiny["main"].global_block()
    calls = [o for o in block.ops if o.type == "fused_attention"]
    by_layer = {}
    for op in calls:
        by_layer.setdefault(op.attrs[fluid.core.ir.NAME_SCOPE_ATTR],
                            []).append(op)
    assert sorted(by_layer) == ["l15.attn", "l17.attn", "l19.cross"]
    assert all(len(v) == 2 for v in by_layer.values())
    for a, b in zip(by_layer["l17.attn"], by_layer["l19.cross"]):
        assert a.input("K") == b.input("K") and a.input("V") == b.input("V")
        assert a.input("Q") != b.input("Q")
    assert by_layer["l15.attn"][0].input("K") \
        != by_layer["l17.attn"][0].input("K")
    k1, k2 = (op.input("K")[0] for op in by_layer["l17.attn"])
    v = by_layer["l17.attn"][0].input("V")[0]
    assert by_layer["l17.attn"][1].input("V")[0] == v and k1 != k2
    (scan16,) = [o for o in block.ops if o.type == "selective_scan"
                 and o.attrs[fluid.core.ir.NAME_SCOPE_ATTR] == "l16.mamba"]
    memory = scan16.output("Out")[0]
    readers = [o for o in block.ops if o.type == "swiglu"
               and memory in o.input("Up")]
    assert sorted(o.attrs[fluid.core.ir.NAME_SCOPE_ATTR] for o in readers) \
        == ["l16.mamba", "l18.gmu"]
    sums = {o.output("Out")[0]: len(o.input("X")) for o in block.ops
            if o.type == "sum" and o.attrs.get("__role__") == "backward"}
    grad = fluid.core.ir.grad_var_name
    assert [sums[grad(n)] for n in (k1, k2, v, memory)] == [2, 2, 4, 2]
    assert census.layer_census(tiny["main"])["activation_grad_fanin_max"] == 4
    assert census.parameter_sharing(tiny["main"])["grad_fanin_max"] == 2


def test_a_window_layer_and_a_full_layer_differ_in_their_window(tiny):
    calls = {}
    for op in tiny["main"].global_block().ops:
        if op.type == "fused_attention":
            calls.setdefault(op.attrs[fluid.core.ir.NAME_SCOPE_ATTR],
                             []).append(op.attrs.get("window"))
    assert calls == {"l15.attn": [48, 48], "l17.attn": [None, None],
                     "l19.cross": [None, None]}


# -- the planted faults --------------------------------------------------------------------------

@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_each_planted_fault_is_refused(tiny, fault):
    """(`untied_head` moves no forward number: the table's gradient
    alone.)"""
    CASE.planted_fault_is_refused(tiny, fault)


def test_the_config_names_every_fault_and_no_other():
    assert sorted(CONFIG["reference"]["check"]["faults"]) == sorted(ref.FAULTS)
    assert len(ref.FAULTS) == 21


def test_an_unknown_fault_is_refused(tiny):
    CASE.unknown_fault_is_refused(tiny)


def test_reference_in_blocks_is_the_reference(tiny):
    CASE.reference_in_blocks_is_the_reference(
        tiny, ["l14.mamba.in.w", "l16.mamba.A_log", "l15.attn.k.w",
               "l17.attn.v.w", "l19.cross.q.w", "l18.gmu.in.w", "embed.w"],
        tol=1e-4, q_block=32, token_block=16)


def test_reference_last_positions_equal_the_full_pass(tiny):
    """(2e-5: the head's product on 16 rows is blocked otherwise than on
    128; read 9e-6.)"""
    CASE.reference_last_positions_equal_the_full_pass(tiny, tol=2e-5)


def test_reference_in_bfloat16_is_another_number(tiny):
    CASE.reference_in_bfloat16_is_another_number(tiny)


# -- AMP -----------------------------------------------------------------------------------------

def test_tiny_model_amp_within_bf16_of_reference():
    """Under AMP the projections, both softmax maps, the MLPs and the head's
    product (the table cast once) are bf16; the embedding's rows, the whole
    scan (its x, dt_raw, B and C widened before the rule), every norm's
    statistics and the loss stay float32. At the initial weights. A bf16
    value carries 8 bits: logits of std ~0.16 here read within 0.003 in the
    mean, the loss within 0.002, a gradient within 5% in the Frobenius norm,
    the scan's small parameters (a few numbers downstream of every rounding)
    within 15%."""
    CASE.amp_within_bf16_of_reference(
        {0.05: ("l14.mamba.in.w", "l16.mamba.out.w", "l15.attn.k.w",
                "l17.attn.v.w", "l19.cross.q.w", "l18.gmu.in.w",
                "l14.mlp.up.w", "embed.w", "final_norm.w"),
         0.15: ("l14.mamba.A_log", "l14.mamba.dt.b", "l14.mamba.conv.b",
                "l14.mamba.D")},
        mean=0.003, most=0.03, of_std=False)


def test_amp_lists_say_what_runs_in_which_precision():
    assert "selective_scan" in registry.AMP_F32_OPS
    assert "fused_attention" in registry.AMP_BF16_OPS
    for op in ("lookup_table", "scale", "causal_conv1d", "layer_norm",
               "rms_norm", "swiglu", "exp"):
        assert op not in registry.AMP_F32_OPS | registry.AMP_BF16_OPS, op


def test_five_adam_steps_lower_the_loss():
    CASE.adam_steps_lower_the_loss()


# -- what the Program holds; spans and counters --------------------------------------------------

SCOPES = {14: "mamba", 15: "attn", 16: "mamba", 17: "attn", 18: "gmu",
          19: "cross"}


@pytest.mark.parametrize("layer", HELD)
def test_every_layer_is_a_mixer_and_an_mlp_under_their_scopes(tiny, layer):
    scopes = _forward_ops_by_scope(tiny["main"])
    assert [k for k in ("mamba", "attn", "gmu", "cross")
            if f"l{layer}.{k}" in scopes] == [SCOPES[layer]]
    ops = scopes[f"l{layer}.{SCOPES[layer]}"]
    kind = KINDS[layer - 14]
    assert ops.count("selective_scan") == (kind == "mamba")
    assert ops.count("causal_conv1d") == (kind == "mamba")
    assert ops.count("fused_attention") == 2 * (kind in ("window", "full",
                                                         "cross"))
    assert ops.count("swiglu") == (kind in ("mamba", "gmu"))
    assert ops.count("expand") == 3 * (kind in ("window", "full"))
    assert ops.count("elementwise_sub") == 2 * ("fused_attention" in ops)
    assert ops.count("rms_norm") == ("fused_attention" in ops)
    mlp = scopes[f"l{layer}.mlp"]
    assert mlp.count("swiglu") == 1 and mlp.count("mul") == 3
    for sub in (ops, mlp):
        assert (sub.count("layer_norm"), sub.count("elementwise_add")) \
            == (1, 1 + (sub is ops) * {"window": 4, "full": 4,
                                        "cross": 2}.get(kind, 0))
        assert "rotary_embedding" not in sub
    assert scopes[None] == ["lookup_table", "layer_norm", "matmul",
                            "softmax_with_cross_entropy", "mean"]


CENSUS = {"layer_kinds": {"selective_scan": 2, "differential_attention": 2,
                          "cross_decoder_attention": 1, "gated_memory": 1},
          "selective_scan_layers": 2, "selective_scan_state": 16,
          "diff_attention_layers": 3, "attention_window_layers": 1,
          "attention_window": 512, "shared_kv_readers": 1,
          "memory_readers": 1, "activation_grad_fanin_max": 4,
          "tied_heads": 1}


def test_layer_census_reads_the_issues_counts():
    """The cell's Program in kinds and counters (at narrow widths: the census
    reads ops, not sizes): the published window of 512 under 1024 tokens,
    16 states."""
    main, _, _, _ = CASE.program(fluid.optimizer.SGD(learning_rate=1e-3),
                                 seq_len=1024, window=512, ssm_state=16)
    got = census.layer_census(main)
    assert got == CENSUS
    for key in ("full_attention", "latent_attention", "window_attention",
                "state_space"):
        assert key not in got["layer_kinds"]
    # a window as long as the sequence is no window
    main, _, _, _ = CASE.program(window=128)
    assert "attention_window_layers" not in census.layer_census(main)


@pytest.fixture(scope="module")
def compile_detail():
    return CASE.compile_detail()


@pytest.mark.parametrize("key,value", [
    ("selective_scan_layers", 2), ("selective_scan_state", 8),
    ("diff_attention_layers", 3), ("attention_window_layers", 1),
    ("attention_window", 48), ("shared_kv_readers", 1),
    ("memory_readers", 1), ("activation_grad_fanin_max", 4),
    ("selective_scan_plan", "plain"), ("tied_heads", 1),
    ("grad_fanin_max", 2)])
def test_compile_event_carries_the_census(compile_detail, key, value):
    carries_the_census(compile_detail, {key: value},
                       absent=["selective_scan_grid_steps"])


@pytest.mark.parametrize("model", ["granite_hybrid", "kanana2", "mellum2",
                                   "nemotron_h", "trinity"])
def test_the_new_keys_go_with_what_they_count(model):
    """No program without a Mamba-1 scan, a differential layer or a reader of
    another layer's tensors gains a key (Kanana-2's values are narrower than
    its keys in ONE call a layer; Granite's and Nemotron-H's scans are
    `ssd_scan`s)."""
    got = census.layer_census(build_program(model)[0])
    new = {"selective_scan_layers", "selective_scan_state",
           "diff_attention_layers", "shared_kv_readers", "memory_readers",
           "activation_grad_fanin_max"}
    assert not new & set(got)
    assert not {"selective_scan", "differential_attention",
                "cross_decoder_attention", "gated_memory"} \
        & set(got["layer_kinds"])


# -- the copies and the harness ------------------------------------------------------------------

def test_the_two_copies_of_the_reference_are_identical():
    CASE.two_copies_of_the_reference_are_identical()


def test_the_config_holds_the_published_widths_and_the_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = []
    if os.path.exists(catalog):     # the builder's machine has it
        with open(catalog) as f:
            rows = [json.loads(line) for line in f]
    want = {"hidden_size": 2560, "intermediate_size": 10240,
            "num_attention_heads": 40, "num_key_value_heads": 20,
            "mb_per_layer": 2, "sliding_window": 512,
            "layer_norm_eps": 1e-05, "tie_word_embeddings": True,
            "mlp_bias": False, "lm_head_bias": False,
            "model_type": "phi4flash", "num_hidden_layers": 6,
            "vocab_size": 25008, "num_hidden_layers_published": 32,
            "vocab_size_published": 200064}
    assert {k: CONFIG[k] for k in want} == want
    for row in rows:        # the catalog's row, key for key but the two cut
        if row["name"] == "Phi-4-mini-flash-reasoning":
            assert CONFIG["source"] == row["source_url"]
            differs = sorted(k for k, v in row["config"].items()
                             if CONFIG.get(k) != v)
            assert differs == ["num_hidden_layers", "vocab_size"]
    assert [r.split()[0] for r in CONFIG["reduced"]] == [
        "num_hidden_layers", "vocab_size"]
    assert "layers 14-19" in CONFIG["reduced"][0]
    args = CONFIG["build_args"]
    assert (args["n_layer"], args["first_layer"], args["layers_held"],
            args["mb_per_layer"], args["window"]) == (32, 14, 6, 2, 512)
    assert (args["d_model"], args["d_ff"], args["n_head"], args["n_kv_head"],
            args["head_dim"], args["ssm_state"], args["conv_kernel"],
            args["expand"], args["dt_rank"], args["vocab_size"]) == \
        (2560, 10240, 40, 20, 64, 16, 4, 2, 160, 25008)
    assert "five pipeline stages" in CONFIG["deployment"]
    assert str(CONFIG["parameters"]) in CONFIG["deployment"].replace(",", "")
    assert CONFIG["parameter_bytes"]["that_stay"] == 12 * CONFIG["parameters"]
    for key in ("Mamba-1's sizes", "Mamba-1 initialisation", "biases",
                "the pairing", "lambda", "optimizer", "precision"):
        assert key in CONFIG["assumed"], key


def test_the_parameter_count_is_the_programs():
    """The configuration's `parameters`, the FLOP module's count and the
    Program's own, at the published widths (nothing runs: shapes alone); and
    the whole model's by the same function is the published 3.8B."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from flops import phi4_flash as counts
    finally:
        sys.path.pop(0)
    args = {**CONFIG["build_args"], "seq_len": 4096}
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        models.phi4_flash.build(**args)
    held = sum(int(np.prod(p.shape))
               for p in main.global_block().all_parameters())
    flops = counts.flops_per_example(**args)
    assert held == flops["parameters"] == CONFIG["parameters"] == 697094272
    whole = counts.flops_per_example(**{**args, "first_layer": 0,
                                        "layers_held": None,
                                        "vocab_size": 200064})
    assert whole["parameters"] == 3852562944
    assert whole["layers"] == {"mamba": 9, "window": 8, "full": 1, "gmu": 7,
                               "cross": 7}
    assert abs(flops["forward_backward"] / 1e12 - 18.0) < 0.01
    assert abs(flops["mlp_share"] - 0.644) < 1e-3
    assert abs(flops["head_share"] - 0.087) < 1e-3
    assert flops["selective_scan_bytes"] == 2 * 3 * 4096 * 43584
    assert counts.layer_kind(16) == "mamba" and counts.layer_kind(19) == "cross"
    assert [counts.layer_kind(l) for l in range(32)] \
        == [models.phi4_flash.layer_kind(l) for l in range(32)]


def test_the_tiny_block_runs_through_the_benchmark():
    runs_through_the_benchmark("phi_4_mini_flash_reasoning.s4096")
