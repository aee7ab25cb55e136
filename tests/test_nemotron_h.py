"""Nemotron-H (layers that are one sublayer each by a pattern string: Mamba-2
state-space mixers through the chunked `ssd_scan`, softmax attention with no
positions at a wide key-value group, two-matrix relu² experts as one chip's
share of a sigmoid-routed layer whose selection bias the step rewrites, a
shared expert) through `layers` -> Program IR -> `Executor`, against the plain
reference (`tests/nemotron_h_reference.py`: the recurrence token by token, a
convolution of shifted products, `jnp.repeat`, a loop over the held experts,
`next_bias`). The sizes are the configuration's `tiny` block. Seeded random
weights, float32, AMP off unless a test says otherwise.

Tolerances: a float32 program against a float32 reference at "highest" agrees
to a few 1e-6 in a product's result; through nine layers, a softmax and the
top-k's renormalisation the logits stay within 1e-4 of their largest value and
a gradient within 2e-4 in the Frobenius norm (`test_trinity.py`'s limits, for
its reason). The chunked scan against the recurrence sums the same products
in another order, exponentials of differences in place of products of
exponentials: 2e-5 of the largest value (RTOL)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models, observe
from paddle_tpu.core import registry
from paddle_tpu.observe import census
from paddle_tpu.ops import decoder_block as db
from paddle_tpu.ops import linear_attention as la
from paddle_tpu.ops import state_space as ss

import nemotron_h_reference as ref
from decoder_case import (SCAN_NAMES, DecoderCase, _forward_ops_by_scope,
                          _planted, _published_scan, _recurrence,
                          _scan_inputs, _scan_layer, carries_the_census,
                          config, frob, rel_err, run_piece,
                          runs_through_the_benchmark, tiny_args)

CONFIG = config("nemotron_h")
GAMMA = 0.001
PATTERN = "MEMEM*EME"
# the pattern's nine layers, hidden 64, 4 state-space heads of 16 in 2 groups
# over a state of 16, 4/2 attention heads of 16, 256 tokens in chunks of 128,
# 16 experts top-3 of width 24, 4 held from expert 4, a shared expert of 48
TINY = tiny_args("nemotron_h")
REF_KW = {k: TINY[k] for k in (
    "layer_pattern", "mamba_heads", "mamba_head_dim", "n_groups", "ssm_state",
    "n_head", "n_kv_head", "head_dim", "top_k", "first_expert",
    "routed_scaling_factor", "rms_eps", "chunk")}
RTOL = 2e-5


def test_the_tiny_block_is_the_issues():
    assert TINY["layer_pattern"] == PATTERN == CONFIG["hybrid_override_pattern"]
    assert (TINY["seq_len"], TINY["chunk"], TINY["d_model"]) == (256, 128, 64)
    assert (TINY["mamba_heads"], TINY["mamba_head_dim"], TINY["n_groups"],
            TINY["ssm_state"]) == (4, 16, 2, 16)
    assert (TINY["n_expert"], TINY["top_k"], TINY["experts_held"],
            TINY["first_expert"]) == (16, 3, 4, 4)
    assert TINY["bias_update_rate"] == GAMMA
    # every size that sets the cost is overridden; what stays is no size
    kept = set(CONFIG["build_args"]) - set(CONFIG["tiny"]["build_args"])
    assert kept == {"layer_pattern", "conv_kernel", "chunk", "time_step",
                    "routed_scaling_factor", "bias_update_rate", "rms_eps",
                    "rescale_layers"}


# -- the scan: chunks against the recurrence -------------------------------------------------

@pytest.mark.parametrize("B,T,chunk", [(1, 64, 64), (1, 256, 64),
                                       (2, 128, 64), (1, 256, 128)],
                         ids=["one_chunk", "four_chunks", "batch_2",
                              "chunk_128"])
def test_chunked_scan_is_the_recurrence(B, T, chunk):
    """Forward and every gradient (xs, B, C, dt through `dt_raw` and
    `dt_bias`, `A_log`, D) of the chunked op against the token-by-token
    recurrence, float32."""
    feed, params = _scan_inputs(B, T, 4, 8, 2, 16)
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


def test_chunks_of_64_and_128_agree():
    feed, params = _scan_inputs(1, 256, 4, 8, 2, 16, seed=1)
    runs = [run_piece(_scan_layer(chunk), feed, params) for chunk in (64, 128)]
    assert rel_err(runs[0][0][0], runs[1][0][0]) < RTOL
    for name in SCAN_NAMES:
        assert frob(runs[0][1][name], runs[1][1][name]) < 1e-4, name


def test_scan_refuses_a_length_off_the_chunk():
    feed, params = _scan_inputs(1, 96, 4, 8, 2, 16)
    with pytest.raises(Exception, match="multiple of the chunk"):
        run_piece(_scan_layer(64), feed, params)


def test_interpreted_kernels_are_the_chunked_form(monkeypatch):
    """`ssd_fwd` and `ssd_bwd` under the Pallas interpreter against the XLA
    form and its `jax.vjp`, at the published head shapes and 256 tokens (two
    chunks, so the state and dS are carried once each way). Both sum float32
    products of the same operands in another order: 1e-5 of the largest
    value forward, 1e-4 in the Frobenius norm backward (the saved states
    bitwise what the next chunk's scratch held)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert ss._plan(64, 128, 8, 128) == "kernel"
    args = _published_scan()
    out, states = ss._ssd_forward(*args, 128)
    want, vjp = jax.vjp(lambda *a: ss.chunked_ssd(*a, 128), *args)
    assert states.shape == (2, 1, 8, 64, 128)
    assert np.all(np.asarray(states[0]) == 0)
    assert rel_err(out, want) < 1e-5
    d_out = jnp.asarray(np.random.RandomState(3).randn(*out.shape),
                        jnp.float32)
    got = ss._ssd_backward(*args, states, d_out, 128)
    for name, g, w in zip(ss._SLOTS, got, vjp(d_out)):
        assert frob(g, w) < 1e-4, name


def test_the_plan_reads_the_shape_alone():
    assert ss._plan(64, 128, 8, 128) == "kernel"
    assert ss._plan(64, 128, 8, 64) == "xla"       # another chunk
    assert ss._plan(16, 16, 2, 128) == "xla"       # the tiny block's heads
    assert ss._plan(64, 128, 7, 128) == "xla"      # heads do not pair up


# -- the convolution's bias, the gate before the grouped norm ----------------------------------

def _conv_want(x, w, b):
    return ref.causal_conv_silu(x, w, b)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_causal_conv_with_a_bias(monkeypatch, kernels):
    """`causal_conv1d(bias_attr=)`: forward, dX, dW and dBias (the column sum
    of the pre-activation's gradient) against jnp; the XLA form at a width
    off the kernels' envelope, the kernels under the interpreter on it."""
    if kernels:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    T, C = (64, 128) if kernels else (24, 6)
    assert (la._conv_plan(T, C, 4) == "kernel") == kernels
    rng = np.random.RandomState(4)
    x = rng.randn(2, T, C).astype(np.float32)
    w = rng.uniform(-0.5, 0.5, (C, 4)).astype(np.float32)
    b = (rng.randn(C) * 0.3).astype(np.float32)
    (y,), grads, probe = run_piece(
        lambda d: [layers.causal_conv1d(
            d["x"], 4, param_attr=fluid.ParamAttr(name="w"),
            bias_attr=fluid.ParamAttr(name="b"))], {"x": x},
        {"w": w, "b": b})
    assert rel_err(y, _conv_want(x, w, b)) < RTOL
    want = jax.grad(lambda *a: jnp.sum(_conv_want(*a) * probe),
                    (0, 1, 2))(x, w, b)
    for name, g in zip(("x", "w", "b"), want):
        assert rel_err(grads[name], g) < 1e-4, name
    # and the bias mattered
    assert rel_err(y, _conv_want(x, w, None)) > 0.01


def test_causal_conv_without_a_bias_has_no_bias_slot():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = layers.data(name="x", shape=[2, 24, 6], dtype="float32",
                        append_batch_size=False)
        layers.causal_conv1d(x, 4)
    (op,) = [o for o in main.global_block().ops if o.type == "causal_conv1d"]
    assert sorted(op.inputs) == ["W", "X"]


def _gate_first_want(x, z, w, groups, eps=1e-5):
    u = x * jax.nn.silu(z)
    g = u.reshape(u.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return g.reshape(u.shape) * w


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_gated_norm_with_the_gate_first_over_groups(monkeypatch, kernels):
    """`gated_rms_norm(gate_first=True, group_size=)`: the gate, then the
    norm over each group, times a weight as wide as all groups; forward, dX,
    dGate and dScale against jnp, XLA form and interpreted kernels."""
    if kernels:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    T, groups, width = (32, 2, 128) if kernels else (24, 3, 8)
    assert (db._gated_norm_plan((2, T, groups, width), jnp.dtype("float32"))
            == "kernel") == kernels
    rng = np.random.RandomState(5)
    x = rng.randn(2, T, groups * width).astype(np.float32)
    z = rng.randn(2, T, groups * width).astype(np.float32)
    w = rng.uniform(0.5, 1.5, groups * width).astype(np.float32)
    (y,), grads, probe = run_piece(
        lambda d: [layers.gated_rms_norm(
            d["x"], d["z"], epsilon=1e-5, gate_first=True, group_size=width,
            param_attr=fluid.ParamAttr(name="w"))], {"x": x, "z": z},
        {"w": w})
    assert y.shape == x.shape
    assert rel_err(y, _gate_first_want(x, z, w, groups)) < RTOL
    want = jax.grad(lambda *a: jnp.sum(_gate_first_want(*a, groups) * probe),
                    (0, 1, 2))(x, z, w)
    for name, g in zip(("x", "z", "w"), want):
        assert rel_err(grads[name], g) < 1e-4, name
    # neither Qwen3-Next's order nor one norm over everything
    other = run_piece(
        lambda d: [layers.gated_rms_norm(
            layers.reshape(d["x"], shape=[0, 0, groups, width]),
            layers.reshape(d["z"], shape=[0, 0, groups, width]),
            epsilon=1e-5, param_attr=fluid.ParamAttr(name="w8"))],
        {"x": x, "z": z})[0][0]
    assert rel_err(other.reshape(y.shape) * w, y) > 0.05
    assert rel_err(_gate_first_want(x, z, w, 1), y) > 0.05


def test_group_size_goes_with_gate_first():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data(name="x", shape=[2, 8, 16], dtype="float32",
                        append_batch_size=False)
        with pytest.raises(ValueError, match="goes with gate_first"):
            layers.gated_rms_norm(x, x, group_size=8)
        with pytest.raises(ValueError, match="do not divide"):
            layers.gated_rms_norm(x, x, gate_first=True, group_size=5)


# -- two-matrix experts; the shares add up -----------------------------------------------------

def _layer_weights(rng, d, n_expert, width, shared):
    whole = {"router.w": rng.randn(d, n_expert),
             "router.bias": rng.randn(n_expert) * 0.3,
             "experts.up.w": rng.randn(n_expert, d, width) * 0.3,
             "experts.down.w": rng.randn(n_expert, width, d) * 0.3,
             "shared.up.w": rng.randn(d, shared) * 0.3,
             "shared.down.w": rng.randn(shared, d) * 0.3}
    return {n: v.astype(np.float32) for n, v in whole.items()}


@pytest.mark.parametrize("n_expert,held,k,width", [(16, 16, 3, 12),
                                                   (16, 4, 3, 12),
                                                   (128, 8, 6, 8)],
                         ids=["whole", "four_shares_of_4",
                              "sixteen_shares_of_8"])
def test_the_shares_add_up_to_the_whole_layer(n_expert, held, k, width):
    """`moe_experts(gated=False, activation="relu2")` against a loop over
    experts: the routed parts that all the shares give (one share: the whole
    layer), plus the shared expert once, are the uncut reference's whole E
    layer: forward, the gradient of the router and of the layer's input. With
    a planted non-zero `b`."""
    d = 16
    rng = np.random.RandomState(5)
    x = rng.randn(40, d).astype(np.float32)
    whole = _layer_weights(rng, d, n_expert, width, 20)
    shares = n_expert // held
    cut = {f"s{j}.{which}.w":
           whole[f"experts.{which}.w"][j * held:(j + 1) * held]
           for j in range(shares) for which in ("up", "down")}

    def build(data):
        routing = layers.moe_router(
            data["x"], n_expert, k, norm_topk_prob=True,
            score_func="sigmoid", norm_eps=1e-20, scaling_factor=2.5,
            param_attr=fluid.ParamAttr(name="router.w"),
            bias_attr=_planted("router.bias", whole["router.bias"]))
        share = {} if shares == 1 else {"experts_held": held}
        parts = [layers.moe_experts(
            data["x"], routing, n_expert, width, name=f"s{j}", gated=False,
            activation="relu2", **share,
            **({"first_expert": j * held} if share else {}))
            for j in range(shares)]

        def fc(v, size, name):
            return layers.fc(v, size, bias_attr=False,
                             param_attr=fluid.ParamAttr(name=name))

        hidden = layers.relu2(fc(data["x"], 20, "shared.up.w"))
        return [layers.sums(parts + [fc(hidden, d, "shared.down.w")])] + parts

    params = {**{n: v for n, v in whole.items()
                 if not n.startswith(("experts.", "router.bias"))}, **cut}
    outs, grads, probe = run_piece(build, {"x": x}, params)
    assert not any(".gate." in n for n in grads)
    kw = dict(top_k=k, scale=2.5)

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
        for j in (0, shares - 1):   # a share alone is the reference given it
            own = {n: (v[j * held:(j + 1) * held]
                       if n.startswith("experts.") else v)
                   for n, v in whole.items()}
            alone = ref.sparse_experts(own, x, first_expert=j * held, **kw)[0]
            assert rel_err(outs[1 + j], alone - shared) < 1e-4, j
        for fault in ("relu_not_squared", "gated_experts", "no_route_scale"):
            bad = ref.sparse_experts(whole, x, first_expert=0, fault=fault,
                                     **kw)[0]
            assert rel_err(bad, want(x, whole["router.w"])) > 0.05, fault
    assert rel_err(grads["x"], gx) < 1e-4
    assert rel_err(grads["router.w"], gr) < 1e-4


@pytest.mark.parametrize("transpose_w", [False, True],
                         ids=["rows_times_stack", "rows_times_transpose"])
def test_a_lane_major_stack_is_multiplied_through_its_transpose(
        monkeypatch, transpose_w):
    """A stack `[4, 128, 96]` (the last axis off the 128-lane tile, the
    middle one on it: `experts.up.w` `[8, 2688, 1856]` in small) goes to the
    megablox kernels swapped, with the other `transpose_rhs`, and its weight
    gradient comes back swapped: under the Pallas interpreter `Out`, dX and
    dW are `lax.ragged_dot`'s and its `jax.vjp`'s, with uneven groups, an
    empty one and a share's unused rows behind them. `[4, 96, 128]` and
    `[4, 128, 128]` go as they are, and without the kernels nothing is
    swapped."""
    from paddle_tpu.ops import moe
    rng = np.random.RandomState(6)
    w = jnp.asarray(rng.randn(4, 128, 96) * 0.3, jnp.float32)
    assert not moe._held_lane_major(w)              # ragged_dot's path
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert moe._held_lane_major(w)
    assert not moe._held_lane_major(w.swapaxes(1, 2))
    assert not moe._held_lane_major(jnp.zeros((4, 128, 128)))
    assert not moe._held_lane_major(jnp.zeros((4, 64, 24)))
    sizes = jnp.asarray([128, 0, 256, 128], jnp.int32)
    rows, used = 640, 512
    x = jnp.asarray(rng.randn(rows, 96 if transpose_w else 128), jnp.float32)
    plain = w.swapaxes(1, 2) if transpose_w else w
    want, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes),
                        x, plain)
    got = moe._grouped_dot(x, w, sizes, transpose_w=transpose_w)
    assert got.shape == want.shape
    assert rel_err(got[:used], want[:used]) < RTOL
    if transpose_w:
        return      # the rows' gradient's form; the grads are the other case's
    g = jnp.asarray(rng.randn(*want.shape), jnp.float32)
    d_x, d_w = moe._grouped_dot_grads(x, w, g, sizes)
    want_x, want_w = vjp(g.at[used:].set(0))
    assert d_w.shape == w.shape
    assert rel_err(d_x[:used], want_x[:used]) < RTOL
    assert rel_err(d_w, want_w) < RTOL
    assert not np.any(np.asarray(d_w[1]))           # nobody chose expert 1


@pytest.mark.parametrize("expert_size,ops", [(96, 2), (128, 0)],
                         ids=["up_128x96", "up_128x128"])
def test_a_lane_major_stack_counts_its_ops(monkeypatch, expert_size, ops):
    """A relu² layer 128 wide over experts of 96: `up` `[4, 128, 96]` takes
    the swapped orientation in its `grouped_matmul` and in that op's grad,
    one count each on the compile event (`moe_lane_major_stacks`); `down`
    `[4, 96, 128]` does not, and experts of 128 leave no such key. The
    layer's output and every gradient are what `ragged_dot`'s path gives."""
    rng = np.random.RandomState(7)
    n, d, n_expert, k = 64, 128, 4, 2
    x = rng.randn(n, d).astype(np.float32)
    index = np.stack([rng.permutation(n_expert)[:k] for _ in range(n)]) \
        .astype(np.int32)
    index[index == 1] = 3                           # expert 1 stays empty
    index[:, 1] = np.where(index[:, 0] == index[:, 1], 0, index[:, 1])
    feed = {"x": x, "index": index,
            "weight": rng.uniform(0.05, 0.4, (n, k)).astype(np.float32),
            "counts": np.bincount(index.reshape(-1), minlength=n_expert)
            .astype(np.int32)}
    weights = {
        "e.up.w": rng.randn(n_expert, d, expert_size).astype(np.float32) * .1,
        "e.down.w": rng.randn(n_expert, expert_size, d).astype(np.float32)
        * .1}
    programs = []

    def build(data):
        routing = {"weight": data["weight"], "index": data["index"],
                   "tokens_per_expert": data["counts"]}
        out = layers.moe_experts(data["x"], routing, n_expert, expert_size,
                                 name="e", gated=False, activation="relu2")
        programs.append(out.block.program)
        return [out]

    (want,), want_grads, _ = run_piece(build, feed, weights)
    assert "moe_lane_major_stacks" not in observe.observatory().latest(
        programs[-1]._uid).detail
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    (got,), grads, _ = run_piece(build, feed, weights)
    detail = observe.observatory().latest(programs[-1]._uid).detail
    assert detail.get("moe_lane_major_stacks", 0) == ops
    assert rel_err(got, want) < RTOL
    assert sorted(grads) == ["e.down.w", "e.up.w", "weight", "x"]
    for name, g in want_grads.items():
        assert grads[name].shape == g.shape
        assert rel_err(grads[name], g) < RTOL, name


def test_an_expert_layer_is_gated_silu_or_ungated_relu2():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data(name="x", shape=[8, 16], dtype="float32",
                        append_batch_size=False)
        routing = layers.moe_router(x, 4, 2)
        with pytest.raises(ValueError, match="gated silu or ungated relu2"):
            layers.moe_experts(x, routing, 4, 8, gated=False)


# -- the model ----------------------------------------------------------------------------------

def _seeded_values(shapes, seed=3):
    """Weights far from their initial values, so that no term of the
    comparison is small by construction: norm weights and D in [0.5, 1.5], a
    router five times as sharp, a planted bias of std 0.2, decays `A_log` in
    log [1, 8], `dt_bias` around -1, a convolution bias of std 0.3, the other
    matrices of std 0.1 (five times the initial)."""
    rng = np.random.RandomState(seed)
    values = {}
    for name in sorted(shapes):
        shape = shapes[name]
        if name.endswith("router.bias"):
            value = rng.randn(*shape) * 0.2
        elif "norm" in name or name.endswith(".D"):
            value = rng.uniform(0.5, 1.5, shape)
        elif name.endswith("router.w"):
            value = rng.randn(*shape) * 0.5
        elif name.endswith("A_log"):
            value = np.log(rng.uniform(1, 8, shape))
        elif name.endswith("dt_bias"):
            value = rng.randn(*shape) * 0.5 - 1.0
        elif name.endswith("conv.b"):
            value = rng.randn(*shape) * 0.3
        elif name.endswith("conv.w"):
            value = rng.uniform(-0.5, 0.5, shape)
        else:
            value = rng.randn(*shape) * 0.1
        values[name] = value.astype(np.float32)
    return values


FETCHES = ["loss", "ce", "logits", "tokens_per_expert"]
E_LAYERS = [i for i, kind in enumerate(PATTERN) if kind == "E"]
BIASES = [f"l{i}.router.bias" for i in E_LAYERS]
# what each planted fault has to move, at least: the logits or a gradient by
# 1% where the true reference is met within 2e-4
FAULT_WRT = ["l0.mamba.in.w", "l0.mamba.A_log", "l0.mamba.dt_bias",
             "l0.mamba.conv.b", "l0.mamba.norm.w", "l2.mamba.D",
             "l5.attn.k.w", "l1.experts.up.w", "l1.router.w", "embed.w"]
CASE = DecoderCase(models.nemotron_h.build, TINY, ref, REF_KW, FETCHES,
                   state=BIASES, seeded_values=_seeded_values,
                   fault_wrt=FAULT_WRT)


@pytest.fixture(scope="module")
def tiny():
    return CASE.tiny_model()


MAMBA = ["mamba.in.w", "mamba.conv.w", "mamba.conv.b", "mamba.A_log",
         "mamba.dt_bias", "mamba.D", "mamba.norm.w", "mamba.out.w"]
ATTN = ["attn.q.w", "attn.k.w", "attn.v.w", "attn.o.w"]
MOE = ["router.w", "experts.up.w", "experts.down.w", "shared.up.w",
       "shared.down.w"]
OF_KIND = {"M": MAMBA, "*": ATTN, "E": MOE}
TRAINED = (["embed.w", "final_norm.w", "head.w"]
           + [f"l{i}.{n}" for i, kind in enumerate(PATTERN)
              for n in ["norm.w"] + OF_KIND[kind]])


def test_tiny_model_has_the_reference_parameters(tiny):
    inner, bc = 4 * 16, 2 * 16
    CASE.has_the_reference_parameters(tiny, TRAINED, {
        "l0.mamba.in.w": (64, 2 * inner + 2 * bc + 4),
        "l0.mamba.conv.w": (inner + 2 * bc, 4),
        "l0.mamba.conv.b": (inner + 2 * bc,), "l2.mamba.A_log": (4,),
        "l2.mamba.dt_bias": (4,), "l2.mamba.D": (4,),
        "l4.mamba.norm.w": (inner,), "l5.attn.q.w": (64, 4 * 16),
        "l5.attn.k.w": (64, 2 * 16), "l5.attn.v.w": (64, 2 * 16),
        "l1.experts.up.w": (4, 64, 24), "l1.experts.down.w": (4, 24, 64),
        "l1.router.w": (64, 16), "l1.shared.up.w": (64, 48)})
    assert not any(".gate." in n for n in tiny["params"])


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
    # the out projections start sqrt(52) times smaller than the others
    for small, plain in (("l0.mamba.out.w", "l0.mamba.in.w"),
                         ("l5.attn.o.w", "l5.attn.q.w"),
                         ("l1.shared.down.w", "l1.shared.up.w"),
                         ("l1.experts.down.w", "l1.experts.up.w")):
        ratio = value(plain).std() / value(small).std()
        assert 0.8 * 52 ** 0.5 < ratio < 1.25 * 52 ** 0.5, (small, ratio)


@pytest.mark.parametrize("name", FETCHES)
def test_tiny_model_output_matches_reference(tiny, name):
    CASE.output_matches_reference(tiny, name)


def test_tiny_routing_sends_most_assignments_elsewhere(tiny):
    CASE.routing_sends_most_assignments_elsewhere(tiny, routed_layers=4)


@pytest.mark.parametrize("name", TRAINED)
def test_tiny_model_gradient_matches_reference(tiny, name):
    CASE.gradient_matches_reference(tiny, name)


@pytest.mark.parametrize("layer", E_LAYERS)
def test_one_step_moves_the_bias_as_next_bias_does(tiny, layer):
    CASE.one_step_moves_the_bias_as_next_bias_does(
        tiny, f"l{layer}.router.bias", GAMMA)


@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_each_planted_fault_is_refused(tiny, fault):
    CASE.planted_fault_is_refused(tiny, fault, loss=1e-5)


def test_the_config_names_every_fault_and_no_other():
    assert sorted(CONFIG["reference"]["check"]["faults"]) == sorted(ref.FAULTS)
    assert len(ref.FAULTS) == 17


def test_an_unknown_fault_is_refused(tiny):
    CASE.unknown_fault_is_refused(tiny)


def test_reference_in_blocks_is_the_reference(tiny):
    CASE.reference_in_blocks_is_the_reference(
        tiny, ["l0.mamba.in.w", "l2.mamba.A_log", "l5.attn.k.w",
               "l3.router.w", "embed.w"], q_block=32, token_block=16)


def test_reference_last_positions_equal_the_full_pass(tiny):
    CASE.reference_last_positions_equal_the_full_pass(tiny)


def test_reference_in_bfloat16_is_another_number(tiny):
    CASE.reference_in_bfloat16_is_another_number(tiny)


def test_tiny_model_amp_within_bf16_of_reference():
    """Under AMP the residual stream, the projections, the scan's x, B and C,
    attention and the experts are bf16; dt, a, the scan's sums and state, the
    router's scores, `b` and every norm's statistics stay float32. At the
    initial weights (a sharper router flips assignments under bf16 inputs).
    A bf16 value carries 8 bits: logits of std ~0.16 here read within 0.01 in
    the mean and 0.12 at most (a token whose assignment flipped moves by an
    expert's whole contribution), the loss within 0.005, a gradient within 5% in the Frobenius norm, the
    decay's and step size's (a few numbers downstream of every rounding)
    within 15%."""
    CASE.amp_within_bf16_of_reference(
        {0.05: ("l0.mamba.in.w", "l0.mamba.out.w", "l5.attn.k.w",
                "l1.shared.up.w", "embed.w", "head.w"),
         0.15: ("l0.mamba.A_log", "l0.mamba.dt_bias", "l0.mamba.conv.b")},
        loss=0.005, mean=0.01, most=0.12, of_std=False)


def test_amp_lists_hold_the_gates_and_leave_the_scan_alone():
    assert "ssd_gates" in registry.AMP_F32_OPS
    assert "moe_router" in registry.AMP_F32_OPS
    for op in ("ssd_scan", "causal_conv1d", "gated_rms_norm", "relu2",
               "rms_norm"):
        assert op not in registry.AMP_F32_OPS | registry.AMP_BF16_OPS


def test_five_adam_steps_lower_the_loss():
    CASE.adam_steps_lower_the_loss()


def test_a_pattern_is_a_string_over_m_e_and_star():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        with pytest.raises(ValueError, match="over M, E and"):
            models.nemotron_h.build(**{**TINY, "layer_pattern": "MEXM"})


# -- what the Program holds; spans and counters -------------------------------------------------

@pytest.mark.parametrize("layer", range(9))
def test_every_layer_is_one_sublayer_under_its_own_scope(tiny, layer):
    """A layer holds ONE of a scan, an attention op and a router, its one
    norm, and no rotary op anywhere."""
    scopes = _forward_ops_by_scope(tiny["main"])
    kind = PATTERN[layer]
    ops = scopes[f"l{layer}." + models.nemotron_h.KINDS[kind]]
    assert [f"l{layer}.{k}" in scopes for k in ("mamba", "moe", "attn")] \
        .count(True) == 1
    assert ops.count("rms_norm") == 1
    assert ops.count("ssd_scan") == (kind == "M")
    assert ops.count("ssd_gates") == (kind == "M")
    assert ops.count("causal_conv1d") == (kind == "M")
    assert ops.count("gated_rms_norm") == (kind == "M")
    assert ops.count("fused_attention") == (kind == "*")
    assert ops.count("moe_router") == (kind == "E")
    assert ops.count("relu2") == (2 if kind == "E" else 0)
    assert ops.count("grouped_matmul") == (2 if kind == "E" else 0)
    assert "rotary_embedding" not in ops and "swiglu" not in ops


CENSUS = {"layer_kinds": {"state_space": 4, "full_attention": 1},
          "state_space_layers": 4, "state_space_groups": 2,
          "state_space_heads_per_group": 2, "state_space_chunk": 128,
          "attention_unrotated_layers": 1,
          "attention_kv_group": 16, "moe_router_score": "sigmoid",
          "moe_router_bias_updates": 4, "moe_experts_routed": 128,
          "moe_experts_held": 8, "moe_expert_activation": "relu2"}
CENSUS_SIZES = dict(n_head=32, n_kv_head=2, head_dim=8, n_expert=128, top_k=6,
                    first_expert=0, experts_held=8)


def test_layer_census_reads_the_issues_counts():
    """4 state-space layers, 1 full-attention layer with no rotary at a
    key-value group of 16, 4 expert layers with 128 routed, 8 held, sigmoid
    scores and 4 bias updates."""
    main, _, _, _ = CASE.program(fluid.optimizer.SGD(learning_rate=1e-3),
                             **CENSUS_SIZES)
    got = census.layer_census(main)
    assert got == CENSUS
    assert "attention_rotary_layers" not in got
    assert "dense_ffn_layers" not in got


@pytest.fixture(scope="module")
def compile_detail():
    return CASE.compile_detail()


@pytest.mark.parametrize("key,value", [
    ("state_space_layers", 4), ("attention_unrotated_layers", 1),
    ("attention_kv_group", 2), ("moe_experts_held", 4),
    ("moe_router_bias_updates", 4), ("ssd_plan", "xla"),
    ("moe_share_bounded_ops", 2 * 4), ("moe_share_bounded_moves", 4 * 4)])
def test_compile_event_carries_the_census(compile_detail, key, value):
    carries_the_census(compile_detail, {key: value})


def test_the_scan_tallies_its_grid_steps(monkeypatch):
    """`ssd_grid_steps` on the compile event where the kernels run: batch x
    groups x chunks, forward and backward ops summed (one group of 8 heads
    of 64 over a state of 128, 256 tokens: 2 x 1 x 2 a call, two calls)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        data = {n: layers.data(name=n, shape=list(s), dtype="float32",
                               append_batch_size=False, stop_gradient=False)
                for n, s in (("x", (2, 256, 8, 64)), ("b", (2, 256, 1, 128)),
                             ("c", (2, 256, 1, 128)), ("dt_raw", (2, 256, 8)))}
        y = layers.ssd_scan(data["x"], data["b"], data["c"], data["dt_raw"])
        fluid.append_backward(layers.reduce_sum(y))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    exe.run(main, feed={n: rng.randn(*v.shape).astype(np.float32) * 0.3
                        for n, v in data.items()},
            fetch_list=[y, "x@GRAD"], scope=scope)
    detail = observe.observatory().latest(main._uid).detail
    assert detail["ssd_plan"] == "kernel"
    assert detail["ssd_grid_steps"] == 2 * (2 * 1 * 2)


@pytest.mark.parametrize("model,want", [
    ("mellum2", {"attention_rotary_layers": 4}),
    ("kanana2", {"attention_rotary_layers": 3}),
    ("qwen3_next", {"attention_rotary_layers": 1}),
    ("trinity", {"attention_rotary_layers": 4,
                 "attention_unrotated_layers": 1})])
def test_the_census_of_the_other_models_is_what_it_was(model, want):
    """A program without state-space layers gains no key: no
    `state_space_layers`, no `moe_expert_activation`, and
    `attention_unrotated_layers` only beside layers that turn."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        getattr(models, model).build(**tiny_args(model))
    got = census.layer_census(main)
    keys = ("attention_rotary_layers", "attention_unrotated_layers",
            "state_space_layers", "moe_expert_activation")
    assert {k: got[k] for k in keys if k in got} == want
    assert "state_space" not in got["layer_kinds"]


# -- the copies and the harness -----------------------------------------------------------------

def test_the_two_copies_of_the_reference_are_identical():
    CASE.two_copies_of_the_reference_are_identical()


def test_the_config_holds_the_published_widths_and_the_cut():
    want = {"hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
            "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4,
            "chunk_size": 128, "num_attention_heads": 32,
            "num_key_value_heads": 2, "head_dim": 128,
            "moe_intermediate_size": 1856,
            "moe_shared_expert_intermediate_size": 3712,
            "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
            "num_hidden_layers": 9, "n_routed_experts": 8,
            "vocab_size": 16384, "num_hidden_layers_published": 52,
            "n_routed_experts_published": 128,
            "vocab_size_published": 131072}
    assert {k: CONFIG[k] for k in want} == want
    assert CONFIG["hybrid_override_pattern_published"].startswith(PATTERN)
    assert len(CONFIG["hybrid_override_pattern_published"]) == 52
    assert [r.split()[0] for r in CONFIG["reduced"]] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert "hybrid_override_pattern" in CONFIG["reduced"][0]
    args = CONFIG["build_args"]
    assert (args["d_model"], args["mamba_heads"], args["mamba_head_dim"],
            args["n_groups"], args["ssm_state"], args["d_expert"],
            args["d_shared"], args["n_expert"], args["experts_held"],
            args["top_k"], args["vocab_size"]) == \
        (2688, 64, 64, 8, 128, 1856, 3712, 128, 8, 6, 16384)
    assert "16 chips share each layer" in CONFIG["deployment"]


def test_the_tiny_block_runs_through_the_benchmark():
    runs_through_the_benchmark("nemotron_3_nano_30b_a3b.s2048")
