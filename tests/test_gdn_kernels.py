"""The gated delta rule's two Pallas kernels (`ops/linear_attention.py`:
`gdn_fwd`, `gdn_bwd`) under the Pallas interpreter on the CPU, at head dims
that fill a vreg and at ones that zero channels fill out to whole lanes (96
/ 192, 64 / 192): against `jax.vjp` of `chunked_gated_delta_rule` (the XLA
form the op keeps outside the kernels' envelope) and against the
token-by-token recurrence of `tests/qwen3_next_reference.py`; the saved
states; the op through a Program with and without the kernels; the plan's
table, with the chunks a grid step takes (`p`: an even count of chunks runs
the paired body, an odd one the same body at p = 1); the grid steps the op
tallies; and the names and shapes the benchmark's patterns find the kernels
by (`benchmark/metrics/gdn_*.json`)."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops import _kernels
from paddle_tpu.ops import linear_attention as la

import qwen3_next_reference as ref
from decoder_case import (REGIMES, RTOL, _instruction, frob, piece_noted,
                          run_piece)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, HK, HV, D, CHUNK = 1, 2, 4, 128, 64
SLOTS = "q k v g beta".split()


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def _inputs(t, regime, seed=0, dk=D, dv=D, hv=HV, beta_over=None):
    """Raw q and k (the kernels normalise them) at 2 key / `hv` value heads;
    beta a sigmoid, or uniform over `beta_over`."""
    rng = np.random.RandomState(seed)
    (gs, go), (bs, bo) = REGIMES[regime]
    q = rng.randn(B, t, HK, dk).astype(np.float32)
    k = rng.randn(B, t, HK, dk).astype(np.float32)
    v = rng.randn(B, t, hv, dv).astype(np.float32)
    a = rng.randn(B, t, hv).astype(np.float32) * gs + go
    g = -np.exp(rng.uniform(-1, 2.5, hv)).astype(np.float32) \
        * np.log1p(np.exp(a))
    beta = 1 / (1 + np.exp(-(rng.randn(B, t, hv) * bs + bo)))
    if beta_over:
        beta = rng.uniform(*beta_over, (B, t, hv))
    return q, k, v, g.astype(np.float32), beta.astype(np.float32)


def _shapes(chunks, dk=D, dv=D):
    return (jax.ShapeDtypeStruct((B, chunks * CHUNK, HK, dk), jnp.float32),
            jax.ShapeDtypeStruct((B, chunks * CHUNK, HV, dv), jnp.float32))


def _prepared(q, k, hv=HV):
    """What the op does before either oracle: l2-norm, scale, a key head
    repeated over its value heads."""
    q = la.l2_normalize(jnp.asarray(q)) * q.shape[-1] ** -0.5
    k = la.l2_normalize(jnp.asarray(k))
    return jnp.repeat(q, hv // HK, 2), jnp.repeat(k, hv // HK, 2)


def _chunked(q, k, v, g, beta):
    return la.chunked_gated_delta_rule(*_prepared(q, k, v.shape[2]), v, g,
                                       beta, CHUNK)


def _recurrence(q, k, v, g, beta):
    return ref.delta_rule(*_prepared(q, k, v.shape[2]), v, g, beta,
                          token_block=64)


def _held_to(oracles, args, probe, out, grads):
    """`out` and the five gradients against each oracle's `jax.vjp` at
    HIGHEST."""
    with jax.default_matmul_precision("highest"):
        for oracle in oracles:
            want, vjp = jax.vjp(oracle, *args)
            assert frob(out, want) < RTOL, oracle.__name__
            for name, got, w in zip(SLOTS, grads, vjp(jnp.asarray(probe))):
                assert np.all(np.isfinite(got)), name
                assert got.shape == w.shape
                assert frob(got, w) < 2e-4, (oracle.__name__, name,
                                             frob(got, w))


@pytest.mark.parametrize("chunks", [1, 2, 3, 4])
@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_kernels_match_both_oracles(regime, chunks, interpreted):
    """Forward and all five input gradients: strong, weak and mixed decay,
    write strengths near 0 and near 1; one pair of chunks a call, the odd
    count that keeps one chunk a step, two pairs (the state handed from a
    step to the next), and a single chunk."""
    assert la._grid(*_shapes(chunks), CHUNK)[1] == (1 if chunks % 2 else 2)
    args = _inputs(chunks * CHUNK, regime)
    probe = np.random.RandomState(9).randn(
        B, chunks * CHUNK, HV, D).astype(np.float32)
    out, states = la._gdn_forward(*args, CHUNK)
    grads = la._gdn_backward(*args, states, probe, CHUNK)
    assert np.all(np.isfinite(out))
    _held_to((_chunked, _recurrence), args, probe, out, grads)


def test_kernels_at_unequal_head_dims(interpreted):
    """Key heads of 256 over value heads of 128: the state is [256, 128]."""
    args = _inputs(2 * CHUNK, "mixed", dk=256)
    probe = np.random.RandomState(9).randn(B, 2 * CHUNK, HV, D).astype(
        np.float32)
    out, states = la._gdn_forward(*args, CHUNK)
    assert states.shape == (2, B, HV, 256, D)
    grads = la._gdn_backward(*args, states, probe, CHUNK)
    _held_to((_chunked,), args, probe, out, grads)


@pytest.mark.parametrize("chunks", [2, 3])
@pytest.mark.parametrize("r", [1, 2])
@pytest.mark.parametrize("dk,dv", [(96, 192), (64, 192)])
def test_kernels_off_the_lane_tile_match_both_oracles(dk, dv, r, chunks,
                                                      interpreted):
    """Head dims that are not whole tiles of 128 lanes (Olmo-Hybrid's 96 /
    192, and 64 / 192): the pair on operands filled out with zero channels
    (`_filled_out`), q's scale the given head dim's, the results cut back.
    `Out`, the five gradients and the saved states at the GIVEN dims, beta
    over (0.1, 1.9) (a negative eigenvalue above 1), one and two value
    heads a key head, a pair of chunks a step and one."""
    t, hv = chunks * CHUNK, r * HK
    q = jax.ShapeDtypeStruct((B, t, HK, dk), jnp.float32)
    v = jax.ShapeDtypeStruct((B, t, hv, dv), jnp.float32)
    assert la._grid(q, v, CHUNK)[1] == (1 if chunks % 2 else 2)
    args = _inputs(t, "mixed", seed=5, dk=dk, dv=dv, hv=hv,
                   beta_over=(0.1, 1.9))
    assert args[4].max() > 1.5 and args[4].min() < 0.5
    probe = np.random.RandomState(9).randn(B, t, hv, dv).astype(np.float32)
    out, states = la._gdn_forward(*args, CHUNK)
    assert out.shape == (B, t, hv, dv)
    assert states.shape == (chunks, B, hv, dk, dv)
    assert states.shape == la._fwd_shapes(q, v, CHUNK)[0].shape
    grads = la._gdn_backward(*args, states, probe, CHUNK)
    _held_to((_chunked, _recurrence), args, probe, out, grads)


def test_saved_states_off_the_lane_tile_are_the_padded_kernels_states(
        interpreted):
    """What `States` holds at 96 / 192 is the `[96, 192]` corner of the
    state the kernel carries at 128 / 256, whose other rows and columns are
    zeros: the same call on hand-padded operands gives it (no q enters a
    state, so its scale does not)."""
    q, k, v, g, beta = _inputs(4 * CHUNK, "mixed", dk=96, dv=192)
    _, states = la._gdn_forward(q, k, v, g, beta, CHUNK)

    def pad(x, width):
        return np.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[-1]),))

    _, wide = la._gdn_forward(pad(q, 128), pad(k, 128), pad(v, 256), g, beta,
                              CHUNK)
    assert wide.shape == (4, B, HV, 128, 256)
    np.testing.assert_array_equal(wide[..., 96:, :], 0.0)
    np.testing.assert_array_equal(wide[..., :, 192:], 0.0)
    np.testing.assert_array_equal(states, wide[..., :96, :192])
    assert float(jnp.abs(states[1:]).max()) > 0


@pytest.mark.parametrize("chunks", [3, 4])
@pytest.mark.parametrize("regime", ["g_near_0", "g_strongly_negative",
                                    "mixed"])
def test_saved_states_are_the_recurrence_states(regime, chunks, interpreted):
    """`States[c]` is the recurrence's state after the tokens before chunk
    c: at a grid step's start and, with four chunks in two pairs, the state
    the second chunk of a pair finds inside its step (c = 1, 3). The
    recurrence gives no state away, so it is read through it: after a
    prefix, Dk more tokens that neither decay nor write (g = 0, beta = 0)
    and ask with the unit vectors: `o_t = S^T e_t` is row t."""
    q, k, v, g, beta = _inputs(chunks * CHUNK, regime)
    _, states = la._gdn_forward(q, k, v, g, beta, CHUNK)
    assert states.shape == (chunks, B, HV, D, D)
    assert states.dtype == jnp.float32
    np.testing.assert_array_equal(states[0], 0.0)
    q_n, k_n = _prepared(q, k)
    ask = jnp.broadcast_to(jnp.eye(D)[None, :, None, :], (B, D, HV, D))
    for c in range(1, chunks):
        cut = c * CHUNK

        def grown(x, tail):
            return jnp.concatenate([jnp.asarray(x)[:, :cut], tail], axis=1)

        nothing = jnp.zeros((B, D, HV), jnp.float32)
        with jax.default_matmul_precision("highest"):
            o = ref.delta_rule(grown(q_n, ask), grown(k_n, ask),
                               grown(v, jnp.zeros((B, D, HV, D))),
                               grown(g, nothing), grown(beta, nothing))
        want = jnp.moveaxis(o[:, cut:], 1, 2)       # [B, Hv, Dk, Dv]
        assert frob(states[c], want) < RTOL, c


def _layer(feed, params, chunk=CHUNK):
    return run_piece(
        lambda d: [layers.gated_delta_rule(
            d["q"], d["k"], d["v"], d["a"], d["b"],
            a_log_attr=fluid.ParamAttr(name="A_log"),
            dt_bias_attr=fluid.ParamAttr(name="dt_bias"), chunk=chunk)],
        feed, params)


def _layer_feed(t=2 * CHUNK, d=D, dv=None):
    rng = np.random.RandomState(2)
    feed = {"q": rng.randn(B, t, HK, d), "k": rng.randn(B, t, HK, d),
            "v": rng.randn(B, t, HV, dv or d), "a": rng.randn(B, t, HV),
            "b": rng.randn(B, t, HV)}
    params = {"A_log": np.log(rng.uniform(0.1, 4, HV)).astype(np.float32),
              "dt_bias": rng.uniform(0.5, 1.5, HV).astype(np.float32)}
    return {n: x.astype(np.float32) for n, x in feed.items()}, params


def test_the_op_gives_the_same_numbers_with_and_without_the_kernels(
        monkeypatch):
    """One op, one grad op (`gated_delta_rule_grad`): the kernels and their
    saved `States` where the backend takes them, the XLA form traced again
    under `jax.vjp` where it does not, and a chunk outside the envelope
    (the rule does not depend on how it is cut)."""
    feed, params = _layer_feed()
    (xla,), xla_grads, _ = _layer(feed, params)
    assert piece_noted("gdn_plan") == "xla"
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    (kernel,), kernel_grads, _ = _layer(feed, params)
    assert piece_noted("gdn_plan") == "kernel"
    (cut32,), cut32_grads, _ = _layer(feed, params, chunk=32)
    assert piece_noted("gdn_plan") == "xla"
    for out, grads in ((kernel, kernel_grads), (cut32, cut32_grads)):
        assert frob(out, xla) < RTOL
        assert sorted(grads) == sorted(xla_grads)
        for name, w in xla_grads.items():
            assert frob(grads[name], w) < 2e-4, name


@pytest.mark.parametrize("dk,dv,filled", [(96, 192, [32, 64]),
                                          (64, 192, [64, 64]),
                                          (128, 128, None)])
def test_the_compile_event_says_what_was_filled(dk, dv, filled, monkeypatch):
    """`gdn_plan` and `gdn_lanes_filled` (the zero channels a key and a value
    head gained; absent where the head dims are whole lanes, and where the
    XLA form runs), and the op off the lane tile gives the XLA form's
    numbers through a Program, `States` declared at the given dims."""
    feed, params = _layer_feed(d=dk, dv=dv)
    (xla,), xla_grads, _ = _layer(feed, params)
    assert piece_noted("gdn_plan") == "xla"
    assert piece_noted("gdn_lanes_filled") is None
    assert piece_noted("gdn_grid_steps") is None
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    (kernel,), kernel_grads, _ = _layer(feed, params)
    assert piece_noted("gdn_plan") == "kernel"
    assert piece_noted("gdn_lanes_filled") == filled
    assert piece_noted("gdn_grid_steps") == 2 * (B * HK * 1)
    assert kernel.shape == xla.shape == (B, 2 * CHUNK, HV, dv)
    assert frob(kernel, xla) < RTOL
    for name, w in xla_grads.items():
        assert kernel_grads[name].shape == w.shape
        assert frob(kernel_grads[name], w) < 2e-4, name


def test_head_dims_that_fill_no_vreg_keep_the_xla_form(interpreted):
    feed, params = _layer_feed(d=8)
    (out,), grads, _ = _layer(feed, params)
    assert piece_noted("gdn_plan") == "xla"
    assert piece_noted("gdn_lanes_filled") is None
    assert np.all(np.isfinite(out)) and sorted(grads) == sorted(
        ["q", "k", "v", "a", "b", "A_log", "dt_bias"])


@pytest.mark.parametrize("dk,dv,chunk,plan", [
    (128, 128, 64, "kernel"), (256, 128, 64, "kernel"),
    (128, 256, 64, "kernel"), (8, 8, 64, "xla"), (32, 16, 64, "xla"),
    (96, 192, 64, "kernel"), (64, 192, 64, "kernel"),
    (64, 128, 64, "kernel"), (128, 64, 64, "kernel"),
    (192, 128, 64, "kernel"), (63, 128, 64, "xla"), (128, 56, 64, "xla"),
    (96, 192, 32, "xla"), (128, 128, 32, "xla"), (128, 128, 128, "xla")])
def test_plan_reads_the_shape_alone(dk, dv, chunk, plan):
    """Head dims of at least half a lane tile run the kernels, filled out
    to whole tiles where they are not; under that, and at another chunk,
    the XLA form."""
    assert la._plan(dk, dv, chunk)[0] == plan
    assert la._plan(dk, dv, chunk, chunks=64, r=2)[0] == plan


@pytest.mark.parametrize("d,filled", [(64, 128), (96, 128), (128, 128),
                                      (129, 256), (192, 256), (256, 256)])
def test_a_head_dim_is_filled_out_to_whole_lanes(d, filled):
    assert la._filled(d) == filled


@pytest.mark.parametrize("dk,dv,chunks,r,p", [
    (128, 128, 64, 2, 2),       # the cell: 64 chunks in 32 pairs
    (128, 128, 2, 2, 2), (128, 128, 4, 1, 2), (256, 128, 6, 2, 2),
    (128, 128, 1, 2, 1), (128, 128, 3, 2, 1), (128, 128, 63, 2, 1),
    (128, 256, 64, 8, 2),       # 11.5 MiB of blocks and state
    (256, 256, 64, 8, 1),       # 17.0 MiB: more than a call has unasked
    (96, 192, 64, 1, 2),        # Olmo-Hybrid's, planned at 128 / 256: 1.9 MiB
    (96, 192, 63, 1, 1), (64, 192, 4, 2, 2), (128, 64, 2, 2, 2),
    (192, 256, 64, 8, 1),       # 17.0 MiB at the filled 256 / 256
    (8, 8, 64, 2, 0), (32, 64, 2, 2, 0)])
def test_plan_pairs_the_chunks_where_they_pair_up_and_fit(dk, dv, chunks, r,
                                                          p):
    """`p`, the chunks a grid step takes, from the chunk count, the value
    heads a key head serves and the head dims alone; 0 where no kernel
    runs."""
    assert la._plan(dk, dv, 64, chunks, r)[1] == p
    if p:
        q = jax.ShapeDtypeStruct((3, chunks * 64, 2, dk), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((3, chunks * 64, 2 * r, dv), jnp.bfloat16)
        assert la._grid(q, v, 64) == ((3, 2, chunks // p), p)


@pytest.mark.parametrize("chunks,steps", [(2, 1), (3, 3), (4, 2)])
def test_the_op_tallies_its_grid_steps_forward_and_grad(chunks, steps,
                                                        interpreted):
    """`gdn_grid_steps` on the compile event: batch x key heads x steps of
    `p` chunks, once from the op and once from its grad op; nothing where
    the rule keeps the XLA form."""
    feed, params = _layer_feed(t=chunks * CHUNK)
    _layer(feed, params)
    assert piece_noted("gdn_grid_steps") == 2 * (B * HK * steps)
    feed, params = _layer_feed(t=chunks * CHUNK, d=8)
    _layer(feed, params)
    assert piece_noted("gdn_grid_steps") is None


def test_a_cpu_backend_takes_the_kernels_only_when_interpreted(monkeypatch):
    monkeypatch.setattr(_kernels, "interpret", lambda: False)
    assert not la._kernels_run(128, 128, 64)
    assert not la._kernels_run(96, 192, 64)
    monkeypatch.setattr(_kernels, "interpret", lambda: True)
    assert la._kernels_run(128, 128, 64)
    assert la._kernels_run(96, 192, 64)
    assert not la._kernels_run(8, 8, 64)


def test_the_program_declares_the_saved_states():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = layers.data(name="q", shape=[1, 256, 2, 128], dtype="float32",
                        append_batch_size=False)
        v = layers.data(name="v", shape=[1, 256, 4, 128], dtype="float32",
                        append_batch_size=False)
        a = layers.data(name="a", shape=[1, 256, 4], dtype="float32",
                        append_batch_size=False)
        out = layers.gated_delta_rule(q, q, v, a, a)
    (op,) = [o for o in main.global_block().ops
             if o.type == "gated_delta_rule"]
    states = main.global_block().var(op.output("States")[0])
    assert tuple(states.shape) == (4, 1, 4, 128, 128)
    assert states.dtype == "float32" and states.stop_gradient
    assert tuple(out.shape) == (1, 256, 4, 128)


def test_the_program_declares_the_saved_states_at_the_given_head_dims():
    """Off the lane tile too `States` is `[T / chunk, B, Hv, Dk, Dv]` at the
    dims the op was given (the variable is built from it on any backend):
    the kernels' fill is theirs."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = layers.data(name="q", shape=[1, 256, 2, 96], dtype="float32",
                        append_batch_size=False)
        v = layers.data(name="v", shape=[1, 256, 2, 192], dtype="float32",
                        append_batch_size=False)
        a = layers.data(name="a", shape=[1, 256, 2], dtype="float32",
                        append_batch_size=False)
        out = layers.gated_delta_rule(q, q, v, a, a)
    (op,) = [o for o in main.global_block().ops
             if o.type == "gated_delta_rule"]
    states = main.global_block().var(op.output("States")[0])
    assert tuple(states.shape) == (4, 1, 2, 96, 192)
    assert tuple(out.shape) == (1, 256, 2, 192)


# -- what the benchmark finds the kernels by ----------------------------------

def _metric(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        return json.load(f)


def _cell_instructions():
    """`gdn_fwd` and `gdn_bwd` as `qwen3_next_80b_a3b.bs1` calls them."""
    bf16 = jnp.bfloat16
    q = jax.ShapeDtypeStruct((1, 4096, 16, 128), bf16)
    v = jax.ShapeDtypeStruct((1, 4096, 32, 128), bf16)
    g = jax.ShapeDtypeStruct((1, 4096, 32), jnp.float32)
    states = jax.ShapeDtypeStruct((64, 1, 32, 128, 128), jnp.float32)
    lines = {}
    for fn, args in ((la._gdn_forward, (q, q, v, g, g)),
                     (la._gdn_backward, (q, q, v, g, g, states, v))):
        jaxpr = jax.make_jaxpr(lambda *a: fn(*a, 64))(*args)
        (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        lines[call.params["name"]] = _instruction(call)
    return lines


def test_the_kernels_stay_inside_the_benchmarks_pattern():
    """`gdn_scan_ms.train` and its roofline share find the rule's
    instructions by the shape of their first result; the kernels are
    counted there only while their first outputs keep such a shape, and
    must not be taken for flash or grouped-matmul kernels."""
    lines = _cell_instructions()
    assert sorted(lines) == ["gdn_bwd", "gdn_fwd"]
    assert lines["gdn_fwd"].startswith("%gdn_fwd.1 = (f32[64,1,32,128,128]{")
    assert lines["gdn_bwd"].startswith("%gdn_bwd.1 = (f32[1,32,64,1,64]{")
    for metric in ("gdn_scan_ms.train", "gdn_scan_roofline_pct.train",
                   "gdn_kernel_ms.train", "gdn_kernel_calls.train"):
        pattern = _metric(metric)["args"]["pattern"]
        for line in lines.values():
            assert re.search(pattern, line), (metric, line)
    for metric in ("hybrid_attention_kernels_ms.train",
                   "share_expert_matmul_ms.train"):
        pattern = _metric(metric)["args"]["pattern"]
        for line in lines.values():
            assert not re.search(pattern, line), (metric, line)


def test_the_grid_steps_metric_loads_and_reads_the_tally():
    """`gdn_grid_steps.train` is a data file over the reader that was there
    (`compile_detail`), under the key the op tallies; the cell's grid is 32
    pairs of chunks a key head, both ways, three layers."""
    spec = _metric("gdn_grid_steps.train")
    assert spec["reader"] == "compile_detail"
    assert spec["args"] == {"key": "gdn_grid_steps"}
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers",
                                       "compile_detail.py"))
    q = jax.ShapeDtypeStruct((1, 4096, 16, 128), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16)
    grid, p = la._grid(q, v, 64)
    assert (grid, p) == ((1, 16, 32), 2)
    assert 3 * 2 * grid[0] * grid[1] * grid[2] == 3072
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == "gdn_grid_steps.train"]
    assert entry == {"name": "gdn_grid_steps.train", "unit": "count",
                     "better": "lower", "source": "program_counter",
                     "layer": "linear attention",
                     "moves": "train_examples_per_s",
                     "workloads": ["qwen3_next_80b_a3b.bs1"]}


@pytest.mark.parametrize("metric,reader", [
    ("gdn_kernel_calls.train", "trace_calls"),
    ("gdn_kernel_ms.train", "trace_ops")])
def test_the_kernel_metrics_load_and_find_the_kernels_alone(metric, reader):
    spec = _metric(metric)
    assert spec["reader"] == reader
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers",
                                       reader + ".py"))
    pattern = spec["args"]["pattern"]
    tail = "(f32[8,128]{1,0}, bf16[8,128]{1,0}) custom-call(%p.1)"
    for name in ("%gdn_fwd.1", "%gdn_bwd.3", "gdn_bwd"):
        assert re.search(pattern, f"{name} = {tail}")
    for name in ("%flash_fwd.1", "%gmm.9", "%tgmm.2", "%fusion.7"):
        assert not re.search(pattern, f"{name} = {tail}")
    assert not re.search(pattern, "%gdn_fwd.1 = f32[8]{0} fusion(%p.1)")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == metric]
    assert entry["layer"] == "linear attention"
    assert entry["workloads"] == ["qwen3_next_80b_a3b.bs1"]
