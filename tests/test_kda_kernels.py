"""The per-channel delta rule's two Pallas kernels (`ops/linear_attention.py`:
`kda_fwd`, `kda_bwd`) under the Pallas interpreter on the CPU, at head dims
that fill a vreg: against `jax.vjp` of `chunked_kda_rule` (the XLA form the
op keeps outside the kernels' envelope) and against the token-by-token
recurrence of `tests/ling3_reference.py`, at one chunk a grid step (`p` = 1:
an odd count of chunks) and two; the saved states; the op through a Program
with and without the kernels, and with and without the `States` slot; what
the compile event says of the plan and of both tallies; and the names the
benchmark's patterns find the kernels by
(`benchmark/metrics/kda_scan_kernel_*.json`)."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.layers.nn import LayerHelper
from paddle_tpu.models._decoder import dt_bias_init
from paddle_tpu.ops import linear_attention as la

import ling3_reference as ref
from decoder_case import _instruction, frob, piece_noted, run_piece

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, H, D, CHUNK = 1, 2, 128, 64
SLOTS = "q k v g beta".split()
DECAYS = ["minus_5_everywhere", "near_0", "initial_values", "whole_range"]
# float32 both sides, HIGHEST products in the oracles: what is left is the
# order of the sums. g at -5 everywhere is the case the middle reference row
# is for (`chunked_kda_rule`): dG is a difference of nearly equal sums there,
# and the XLA form itself reads 1e-4 against the recurrence
RTOL = 2e-5


def _limit(name, decay):
    return 1e-3 if (name, decay) == ("g", "minus_5_everywhere") else 2e-4


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def _decay(rng, t, decay):
    """g [B, t, H, D] <= 0: at the bound in every channel (a chunk's running
    sum reaches -320), near 0, as `kda_gates` gives it at the
    configuration's initial values (`A_log` the log of uniform(1, 16),
    `dt_bias` the inverse softplus of [0.001, 0.1]: -1e-10 in most channels,
    live where `exp(A_log)` is near 1), and over the whole of (-5, 0)."""
    shape = (B, t, H, D)
    if decay == "minus_5_everywhere":
        return np.full(shape, -5.0)
    if decay == "near_0":
        return -1e-3 * rng.uniform(0, 1, shape)
    if decay == "whole_range":
        return -5.0 * rng.uniform(0, 1, shape)
    rate = rng.uniform(1.0, 16.0, (H, 1))
    bias = dt_bias_init(H * D, 0).reshape(H, D)
    f = rng.randn(*shape) * 0.5
    return -5.0 / (1.0 + np.exp(-rate * (f + bias)))


def _inputs(t, decay, seed=0):
    rng = np.random.RandomState(seed)
    f32 = jnp.float32
    q, k, v = (jnp.asarray(rng.randn(B, t, H, D), f32) for _ in range(3))
    return [q, k, v, jnp.asarray(_decay(rng, t, decay), f32),
            jnp.asarray(rng.uniform(0, 1, (B, t, H)), f32)]


def _prepared(q, k):
    return (ref.l2_normalize(q) * q.shape[-1] ** -0.5, ref.l2_normalize(k))


def _chunked(q, k, v, g, beta):
    return la._kda_rule(q, k, v, g, beta, CHUNK)


def _recurrence(q, k, v, g, beta):
    return ref.delta_rule(*_prepared(q, k), v, g, beta, token_block=64)


@pytest.mark.parametrize("chunks", [2, 3])
@pytest.mark.parametrize("decay", DECAYS)
def test_kernels_match_both_oracles(decay, chunks, interpreted):
    """Forward and all five input gradients (g's per key channel): one pair
    of chunks a call (`p` = 2, the state handed on inside the step) and the
    odd count that keeps one chunk a step (`p` = 1, the state handed from a
    step to the next)."""
    t = chunks * CHUNK
    shapes = jax.ShapeDtypeStruct((B, t, H, D), jnp.float32)
    assert la._grid(shapes, shapes, CHUNK) \
        == ((B, H, chunks // (2 - chunks % 2)), 2 - chunks % 2)
    args = _inputs(t, decay)
    probe = jnp.asarray(np.random.RandomState(9).randn(B, t, H, D),
                        jnp.float32)
    out, states = la._kda_forward(*args, CHUNK)
    grads = la._kda_backward(*args, states, probe, CHUNK)
    assert np.all(np.isfinite(out))
    with jax.default_matmul_precision("highest"):
        for oracle in (_chunked, _recurrence):
            want, vjp = jax.vjp(oracle, *args)
            assert frob(out, want) < RTOL, oracle.__name__
            for name, got, w in zip(SLOTS, grads, vjp(probe)):
                assert np.all(np.isfinite(got)), name
                assert got.shape == w.shape and got.dtype == w.dtype
                assert frob(got, w) < _limit(name, decay), (
                    oracle.__name__, name, frob(got, w))


@pytest.mark.parametrize("chunks", [3, 4])
@pytest.mark.parametrize("decay", ["near_0", "whole_range"])
def test_saved_states_are_the_recurrence_states(decay, chunks, interpreted):
    """`States[c]` is the recurrence's state after the tokens before chunk
    c: at a grid step's start and, with four chunks in two pairs, the state
    the second chunk of a pair finds inside its step (c = 1, 3). The
    recurrence gives no state away, so it is read through it: after a
    prefix, Dk more tokens that neither decay nor write (g = 0, beta = 0)
    and ask with the unit vectors: `o_t = S^T e_t` is row t."""
    q, k, v, g, beta = _inputs(chunks * CHUNK, decay)
    _, states = la._kda_forward(q, k, v, g, beta, CHUNK)
    assert states.shape == (chunks, B, H, D, D)
    assert states.dtype == jnp.float32
    np.testing.assert_array_equal(states[0], 0.0)
    q_n, k_n = _prepared(q, k)
    ask = jnp.broadcast_to(jnp.eye(D)[None, :, None, :], (B, D, H, D))
    for c in range(1, chunks):
        cut = c * CHUNK

        def grown(x, tail):
            return jnp.concatenate([jnp.asarray(x)[:, :cut], tail], axis=1)

        with jax.default_matmul_precision("highest"):
            o = ref.delta_rule(grown(q_n, ask), grown(k_n, ask),
                               grown(v, jnp.zeros((B, D, H, D))),
                               grown(g, jnp.zeros((B, D, H, D))),
                               grown(beta, jnp.zeros((B, D, H))))
        want = jnp.moveaxis(o[:, cut:], 1, 2)       # [B, H, Dk, Dv]
        assert frob(states[c], want) < RTOL, c


# -- the op through a Program -------------------------------------------------

def _layer(feed, params, chunk=CHUNK):
    return run_piece(
        lambda d: [layers.kda_delta_rule(
            d["q"], d["k"], d["v"], d["f"], d["b"], chunk=chunk,
            a_log_attr=fluid.ParamAttr(name="A_log"),
            dt_bias_attr=fluid.ParamAttr(name="dt_bias"))],
        feed, params)


def _layer_feed(t=2 * CHUNK, d=D):
    rng = np.random.RandomState(2)
    f32 = np.float32
    feed = {n: rng.randn(B, t, H, d).astype(f32) for n in "qkv"}
    feed["f"] = rng.randn(B, t, H * d).astype(f32)
    feed["b"] = rng.randn(B, t, H).astype(f32)
    params = {"A_log": np.log(rng.uniform(0.5, 2, H)).astype(f32),
              "dt_bias": rng.uniform(-2, 2, H * d).astype(f32)}
    return feed, params


def test_the_op_gives_the_same_numbers_with_and_without_the_kernels(
        monkeypatch):
    """One op, one grad op (`kda_delta_rule_grad`): the kernels and their
    saved `States` where the backend takes them, the XLA form traced again
    under `jax.vjp` where it does not, and a chunk outside the envelope
    (the rule does not depend on how it is cut)."""
    feed, params = _layer_feed()
    (xla,), xla_grads, _ = _layer(feed, params)
    assert piece_noted("kda_plan") == "xla"
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    (kernel,), kernel_grads, _ = _layer(feed, params)
    assert piece_noted("kda_plan") == "kernel"
    (cut32,), cut32_grads, _ = _layer(feed, params, chunk=32)
    assert piece_noted("kda_plan") == "xla"
    for out, grads in ((kernel, kernel_grads), (cut32, cut32_grads)):
        assert frob(out, xla) < RTOL
        assert sorted(grads) == sorted(xla_grads)
        for name, w in xla_grads.items():
            assert frob(grads[name], w) < 2e-4, name


def test_head_dims_that_fill_no_vreg_keep_the_xla_form(interpreted):
    feed, params = _layer_feed(d=8)
    (out,), grads, _ = _layer(feed, params)
    assert piece_noted("kda_plan") == "xla"
    assert piece_noted("kda_kernel_grid_steps") is None
    assert np.all(np.isfinite(out)) and sorted(grads) == sorted(
        ["q", "k", "v", "f", "b", "A_log", "dt_bias"])


@pytest.mark.parametrize("chunks,steps", [(2, 1), (3, 3), (4, 2)])
def test_the_compile_event_holds_the_plan_and_both_tallies(chunks, steps,
                                                           interpreted):
    """`kda_grid_steps` keeps its meaning (batch x heads x chunks a call,
    whatever runs it); `kda_kernel_grid_steps` is what the two kernel calls
    really ran: batch x heads x steps of `p` chunks, once from the op and
    once from its grad op."""
    feed, params = _layer_feed(t=chunks * CHUNK)
    _layer(feed, params)
    assert piece_noted("kda_plan") == "kernel"
    assert piece_noted("kda_grid_steps") == 2 * (B * H * chunks)
    assert piece_noted("kda_kernel_grid_steps") == 2 * (B * H * steps)


def _rule_alone(states):
    """The op on fed g and beta, with or without its `States` slot."""
    def build(d):
        helper = LayerHelper("kda_delta_rule")
        new = helper.create_variable_for_type_inference
        out = new(d["v"].dtype)
        outputs = {"Out": [out.name]}
        if states:
            outputs["States"] = [new("float32", stop_gradient=True).name]
        helper.append_op(
            "kda_delta_rule",
            inputs={"Q": [d["q"].name], "K": [d["k"].name],
                    "V": [d["v"].name], "G": [d["g"].name],
                    "Beta": [d["beta"].name]},
            outputs=outputs, attrs={"chunk": CHUNK})
        return [out]
    return build


def test_a_program_built_without_the_slot_still_trains(interpreted):
    """A program from before `States` existed: the forward kernel's states
    have no variable to go to, and the grad op takes `jax.vjp` of the XLA
    form; the same five gradients as the kernel gives on its saved
    states (dG per key channel, fed here as data)."""
    feed = {n: np.asarray(x) for n, x in
            zip(SLOTS, _inputs(2 * CHUNK, "whole_range", seed=3))}
    (with_slot,), kernel_grads, _ = run_piece(_rule_alone(True), feed)
    assert piece_noted("kda_plan") == "kernel"
    kernel_steps = piece_noted("kda_kernel_grid_steps")
    (without,), vjp_grads, _ = run_piece(_rule_alone(False), feed)
    assert piece_noted("kda_plan") == "xla"      # the grad op's, the last
    assert piece_noted("kda_kernel_grid_steps") == kernel_steps // 2
    assert frob(without, with_slot) == 0.0      # the same forward kernel
    assert sorted(vjp_grads) == sorted(kernel_grads) == sorted(SLOTS)
    for name, w in vjp_grads.items():
        assert frob(kernel_grads[name], w) < 2e-4, name


def test_the_program_declares_the_saved_states():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = layers.data(name="q", shape=[1, 256, 4, 128], dtype="float32",
                        append_batch_size=False)
        f = layers.data(name="f", shape=[1, 256, 512], dtype="float32",
                        append_batch_size=False)
        b = layers.data(name="b", shape=[1, 256, 4], dtype="float32",
                        append_batch_size=False)
        out = layers.kda_delta_rule(q, q, q, f, b)
    (op,) = [o for o in main.global_block().ops
             if o.type == "kda_delta_rule"]
    states = main.global_block().var(op.output("States")[0])
    assert tuple(states.shape) == (4, 1, 4, 128, 128)
    assert states.dtype == "float32" and states.stop_gradient
    assert tuple(out.shape) == (1, 256, 4, 128)


# -- what the benchmark finds the kernels by ----------------------------------

def _metric(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        return json.load(f)


def _cell_instructions():
    """`kda_fwd` and `kda_bwd` as `ling_3_0_flash_vl.s2048` calls them, as a
    TPU trace names them (`decoder_case._instruction`)."""
    x = jax.ShapeDtypeStruct((1, 2048, 32, 128), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, 2048, 32, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, 2048, 32), jnp.float32)
    states = jax.ShapeDtypeStruct((32, 1, 32, 128, 128), jnp.float32)
    lines = {}
    for fn, args in ((la._kda_forward, (x, x, x, g, beta)),
                     (la._kda_backward, (x, x, x, g, beta, states, x))):
        jaxpr = jax.make_jaxpr(lambda *a: fn(*a, 64))(*args)
        (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        lines[call.params["name"]] = _instruction(call)
    return lines


def test_the_kernels_stay_inside_their_pattern_and_outside_gdns():
    """`kda_scan_kernel_ms.train` / `kda_scan_kernel_calls.train` find the
    two custom calls by name; no pattern of the scalar rule's cell, by name
    or by the shape of a first result, takes them for `gdn_fwd` / `gdn_bwd`,
    nor do the flash and grouped-matmul patterns."""
    lines = _cell_instructions()
    assert sorted(lines) == ["kda_bwd", "kda_fwd"]
    assert lines["kda_fwd"].startswith("%kda_fwd.1 = (f32[32,1,32,128,128]{")
    assert lines["kda_bwd"].startswith("%kda_bwd.1 = (f32[1,2048,4096]{")
    for metric in ("kda_scan_kernel_ms.train", "kda_scan_kernel_calls.train"):
        pattern = _metric(metric)["args"]["pattern"]
        for line in lines.values():
            assert re.search(pattern, line), (metric, line)
        for name in ("%gdn_fwd.1", "%gdn_bwd.3", "%flash_fwd.1", "%fusion.7"):
            assert not re.search(pattern, f"{name} = (f32[8,128]{{1,0}}) "
                                          f"custom-call(%p.1)")
    for metric in ("gdn_scan_ms.train", "gdn_scan_roofline_pct.train",
                   "gdn_kernel_ms.train", "gdn_kernel_calls.train",
                   "hybrid_attention_kernels_ms.train",
                   "share_expert_matmul_ms.train"):
        pattern = _metric(metric)["args"]["pattern"]
        for line in lines.values():
            assert not re.search(pattern, line), (metric, line)


def test_the_cells_grid_is_what_the_tallies_say():
    """At the cell's shapes: 16 pairs of chunks a head; the op and its grad
    op of five layers tally 10240 chunk steps (`kda_grid_steps.train`'s
    file) and 5120 kernel grid steps."""
    x = jax.ShapeDtypeStruct((1, 2048, 32, 128), jnp.bfloat16)
    grid, p = la._grid(x, x, 64)
    assert (grid, p) == ((1, 32, 16), 2)
    assert 5 * 2 * 1 * 32 * 32 == 10240
    assert 5 * 2 * grid[0] * grid[1] * grid[2] == 5120
    spec = _metric("kda_grid_steps.train")
    assert spec["reader"] == "compile_detail"
    assert spec["args"] == {"key": "kda_grid_steps"}
