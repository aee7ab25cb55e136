"""Ling-3.0-flash-VL's language model (Kimi Delta Attention through the chunked
`kda_delta_rule` in five layers of six, latent attention under a head-wise
gate in the sixth, a leading dense layer, gated-silu experts as one chip's
share of a group-limited sigmoid-routed layer whose selection bias the step
rewrites, a shared expert; built as a run of published layers from
`first_layer` on) through `layers` -> Program IR -> `Executor`, against the
plain reference (`tests/ling3_reference.py`: the recurrence token by token, a
convolution of shifted products, `jnp.repeat`, the groups as `top_k`s and
masks, a loop over the held experts, `next_bias`). The sizes are the
configuration's `tiny` block. Seeded random weights, float32, AMP off unless
a test says otherwise.

Tolerances: a float32 program against a float32 reference at "highest" agrees
to a few 1e-6 in a product's result; through six layers, a softmax and the
top-k's renormalisation the logits stay within 1e-4 of their largest value
(`test_trinity.py`'s limit, for its reason). A gradient stays within 1e-3 in
the Frobenius norm (GRAD_TOL): each chunked rule's own gradients read 1e-6 to
1e-4 against the recurrence's, and a gradient of the first layers passes back
through five of them (read: 2e-4 to 6e-4, growing towards layer 0; the
siblings with one kind of chunked mixer in four hold 2e-4). A planted fault
has to move something ten times that. The chunked rule against the
recurrence sums the same products
in another order, exponentials of differences in place of products of
exponentials: 2e-5 of the largest value forward (RTOL), 1e-4 in the Frobenius
norm for a gradient. At g = -5 in EVERY channel the state is forgotten within
three tokens and g's gradient is 0.004 of the others' size, a sum of terms
near float32's rounding of theirs: 1e-3 there."""

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
from paddle_tpu.ops import moe

import ling3_reference as ref
from decoder_case import (DecoderCase, _forward_ops_by_scope, _planted,
                          config, frob, rel_err, run_piece, tiny_args)

CONFIG = config("ling3")
GAMMA = 0.001
# six layers from the published layer 1 on (KDA + dense, KDA + MoE x 3, MLA +
# MoE, KDA + MoE), hidden 64, 4 heads of 16, a latent row of 32 with 16 + 8 /
# 16 heads, 256 tokens in chunks of 64, 16 experts in 4 groups of which 2
# stay, top-3 of width 24, 4 held from expert 4, a shared expert of 24
TINY = tiny_args("ling3")
REF_KW = {k: TINY[k] for k in (
    "n_layer", "first_layer", "layer_group_size", "n_dense_layer", "n_head",
    "kda_lower_bound", "qk_nope_dim", "qk_rope_dim", "v_head_dim",
    "rope_theta", "top_k", "n_group", "topk_group", "first_expert",
    "routed_scaling_factor", "rms_eps", "chunk")}
RTOL = 2e-5
GRAD_TOL = 1e-3
KINDS = ["kda", "kda", "kda", "kda", "mla", "kda"]      # built layers 0..5


def test_the_tiny_block_is_the_issues():
    assert (TINY["seq_len"], TINY["chunk"], TINY["d_model"]) == (256, 64, 64)
    assert (TINY["n_layer"], TINY["first_layer"], TINY["layer_group_size"],
            TINY["n_dense_layer"]) == (6, 1, 6, 2)
    assert (TINY["n_expert"], TINY["n_group"], TINY["topk_group"],
            TINY["top_k"], TINY["experts_held"], TINY["first_expert"]) \
        == (16, 4, 2, 3, 4, 4)
    assert TINY["bias_update_rate"] == GAMMA
    assert [models.ling3.layer_kind(1 + i, 6) for i in range(6)] == KINDS
    # every size that sets the cost is overridden; what stays is no size
    kept = set(CONFIG["build_args"]) - set(CONFIG["tiny"]["build_args"])
    assert kept == {"n_layer", "first_layer", "layer_group_size",
                    "n_dense_layer", "conv_kernel", "kda_lower_bound",
                    "chunk", "rope_theta", "routed_scaling_factor",
                    "bias_update_rate", "rms_eps"}


def test_the_config_holds_the_published_widths_and_three_cuts():
    build = CONFIG["build_args"]
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"],
            CONFIG["head_dim"], CONFIG["kv_lora_rank"],
            CONFIG["qk_nope_head_dim"], CONFIG["qk_rope_head_dim"],
            CONFIG["v_head_dim"], CONFIG["intermediate_size"],
            CONFIG["moe_intermediate_size"],
            CONFIG["moe_shared_expert_intermediate_size"],
            CONFIG["num_experts_per_tok"], CONFIG["n_group"],
            CONFIG["topk_group"], CONFIG["short_conv_kernel_size"],
            CONFIG["kda_lower_bound"], CONFIG["layer_group_size"]) \
        == (2560, 32, 128, 512, 128, 64, 128, 6144, 768, 768, 8, 8, 4, 4, -5,
            6) \
        == (build["d_model"], build["n_head"], build["head_dim"],
            build["kv_rank"], build["qk_nope_dim"], build["qk_rope_dim"],
            build["v_head_dim"], build["d_dense"], build["d_expert"],
            build["d_shared"], build["top_k"], build["n_group"],
            build["topk_group"], build["conv_kernel"],
            build["kda_lower_bound"], build["layer_group_size"])
    assert [r.split(" ")[0] for r in CONFIG["reduced"]] \
        == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (6, 8, 19648)
    assert (CONFIG["num_hidden_layers_published"],
            CONFIG["num_experts_published"],
            CONFIG["vocab_size_published"]) == (42, 512, 157184)
    assert (build["n_expert"], build["experts_held"], build["first_layer"],
            build["n_layer"], build["vocab_size"]) == (512, 8, 1, 6, 19648)
    assert "767,006,496" in CONFIG["deployment"]


# -- the rule: chunks against the recurrence -----------------------------------------------

def _rule_inputs(B, T, H, D, decay, seed=0):
    rng = np.random.RandomState(seed)
    f = jnp.float32
    g = -5.0 * rng.uniform(0, 1, (B, T, H, D)) if decay == "whole_range" \
        else np.full((B, T, H, D), -5.0)
    return [jnp.asarray(rng.randn(B, T, H, D), f),
            jnp.asarray(rng.randn(B, T, H, D), f),
            jnp.asarray(rng.randn(B, T, H, D), f), jnp.asarray(g, f),
            jnp.asarray(rng.uniform(0, 1, (B, T, H)), f)]


def _recurrence(q, k, v, g, beta):
    q = ref.l2_normalize(q) * q.shape[-1] ** -0.5
    return ref.delta_rule(q, ref.l2_normalize(k), v, g, beta)


RULE_NAMES = ["q", "k", "v", "g", "beta"]


@pytest.mark.parametrize("decay", ["whole_range", "minus_5_everywhere"])
@pytest.mark.parametrize("B,T,H,D", [(1, 64, 2, 8), (1, 256, 2, 8),
                                     (2, 128, 2, 8), (1, 128, 1, 128)],
                         ids=["one_chunk", "four_chunks", "batch_2",
                              "published_head"])
def test_chunked_rule_is_the_recurrence(B, T, H, D, decay):
    """Forward and every gradient (q, k, v, g per channel, beta) of the
    chunked form (the l2-norms included) against the token-by-token
    recurrence, float32; with g drawn over the whole of (-5, 0), and at the
    bound in every channel, where a 64-token chunk's running sum reaches
    -320 and every output and gradient must still be finite."""
    args = _rule_inputs(B, T, H, D, decay)
    probe = jnp.asarray(np.random.RandomState(9).randn(B, T, H, D),
                        jnp.float32)
    got = la._kda_rule(*args, 64)
    grads = jax.grad(lambda *a: jnp.sum(la._kda_rule(*a, 64) * probe),
                     range(5))(*args)
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*args)
        want_grads = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * probe),
                              range(5))(*args)
    assert np.all(np.isfinite(got))
    assert rel_err(got, want) < RTOL
    for name, g, w in zip(RULE_NAMES, grads, want_grads):
        assert np.all(np.isfinite(g)), name
        limit = 1e-3 if (name, decay) == ("g", "minus_5_everywhere") else 1e-4
        assert frob(g, w) < limit, name


def test_no_exponent_passes_forty():
    """What the 16-row blocks are for: every `exp` of the chunked form takes
    an argument <= 8 x 5 = 40 at g = -5 everywhere (a whole 64-token chunk
    relative to one row would ask for exp(315) = inf), and none above 0
    outside the blocks on a tile's diagonal."""
    # (the issue allows 80: a block relative to its FIRST row; the middle
    # row halves that, `chunked_kda_rule` says why)
    args = _rule_inputs(1, 64, 1, 8, "minus_5_everywhere")
    seen = []
    real = jnp.exp

    def spy(x):
        if not isinstance(x, jax.core.Tracer):  # the scan's own: a chunk's
            seen.append(float(jnp.max(x)))      # last running sum, <= 0
        return real(x)

    try:
        jnp.exp = spy
        la.chunked_kda_rule(*args, 64)
    finally:
        jnp.exp = real
    # the two halves of a diagonal block's tiles, relative to its middle
    # row: rows before it on one side, keys after it on the other
    assert sorted(seen)[-2:] == [35.0, 40.0]
    assert len(seen) >= 6 and sorted(seen)[-3] <= 0.0


def test_a_decay_constant_over_the_channels_is_the_gated_delta_rule():
    """With every key channel of a head at the same g the rule is
    `gated_delta_rule`'s on the same inputs."""
    q, k, v, g, beta = _rule_inputs(1, 128, 2, 8, "whole_range", seed=1)
    q = la.l2_normalize(q) * 8 ** -0.5
    k = la.l2_normalize(k)
    scalar = g[..., 0]
    got = la.chunked_kda_rule(q, k, v, jnp.broadcast_to(
        scalar[..., None], g.shape), beta, 64)
    want = la.chunked_gated_delta_rule(q, k, v, scalar, beta, 64)
    assert rel_err(got, want) < RTOL
    # and a decay that differs over the channels is another function
    assert rel_err(la.chunked_kda_rule(q, k, v, g, beta, 64), want) > 0.01


def _rule_layer(chunk=64):
    def build(d):
        return [layers.kda_delta_rule(
            d["q"], d["k"], d["v"], d["f"], d["b"], chunk=chunk,
            a_log_attr=fluid.ParamAttr(name="A_log"),
            dt_bias_attr=fluid.ParamAttr(name="dt_bias"))]
    return build


def _gates_want(f, b, A_log, dt_bias, heads):
    raw = (f + dt_bias).reshape(f.shape[:-1] + (heads, -1))
    return -5.0 * jax.nn.sigmoid(jnp.exp(A_log)[:, None] * raw), \
        jax.nn.sigmoid(b)


def test_the_layer_is_its_gates_and_the_rule():
    """`layers.kda_delta_rule` through the Program (`kda_gates`, then the
    rule with its registered grad): the output and the gradient of every
    input and of `A_log` and `dt_bias` against jnp and the recurrence."""
    B, T, H, D = 1, 128, 2, 8
    rng = np.random.RandomState(2)
    f32 = np.float32
    feed = {n: rng.randn(B, T, H, D).astype(f32) for n in "qkv"}
    feed["f"] = rng.randn(B, T, H * D).astype(f32)
    feed["b"] = rng.randn(B, T, H).astype(f32)
    params = {"A_log": np.log(rng.uniform(0.5, 2, H)).astype(f32),
              "dt_bias": rng.uniform(-2, 2, H * D).astype(f32)}
    (y,), grads, probe = run_piece(_rule_layer(), feed, params)
    names = ["q", "k", "v", "f", "b", "A_log", "dt_bias"]

    def want(q, k, v, f, b, A_log, dt_bias):
        g, beta = _gates_want(f, b, A_log, dt_bias, H)
        return _recurrence(q, k, v, g, beta)

    args = [jnp.asarray({**feed, **params}[n]) for n in names]
    with jax.default_matmul_precision("highest"):
        assert rel_err(y, want(*args)) < RTOL
        want_grads = jax.grad(lambda *a: jnp.sum(want(*a) * probe),
                              range(len(args)))(*args)
    for name, g in zip(names, want_grads):
        assert frob(grads[name], g) < 1e-4, name
    g, _ = _gates_want(*args[3:], H)
    assert float(g.min()) < -4.0 and float(g.max()) > -1.0   # a live decay


def test_kda_gates_against_jnp():
    rng = np.random.RandomState(3)
    f = rng.randn(2, 6, 12).astype(np.float32) * 2
    b = rng.randn(2, 6, 3).astype(np.float32)
    A_log = np.log(rng.uniform(0.5, 4, 3)).astype(np.float32)
    dt_bias = rng.randn(12).astype(np.float32)
    got = la._kda_gates(_attrs(lower_bound=-5.0), f, b, A_log, dt_bias)
    g, beta = _gates_want(f, b, A_log, dt_bias, 3)
    assert got["G"].shape == (2, 6, 3, 4) and got["G"].dtype == jnp.float32
    assert rel_err(got["G"], g) < 1e-6 and rel_err(got["Beta"], beta) < 1e-6
    assert float(got["G"].min()) > -5.0 and float(got["G"].max()) < 0.0
    low = la._kda_gates(_attrs(lower_bound=-5.0), f.astype(jnp.bfloat16),
                        b.astype(jnp.bfloat16), A_log, dt_bias)
    assert low["G"].dtype == low["Beta"].dtype == jnp.float32


def test_the_rule_refuses_a_length_off_the_chunk_and_unequal_heads():
    q = jnp.zeros((1, 96, 2, 8))
    with pytest.raises(ValueError, match="multiple of the chunk"):
        la._kda_delta_rule(_attrs(chunk=64), q, q, q, q, q[..., 0])
    q = jnp.zeros((1, 64, 2, 8))
    with pytest.raises(ValueError, match="as many value heads"):
        la._kda_delta_rule(_attrs(chunk=64), q, q, q[:, :, :1], q, q[..., 0])
    with pytest.raises(ValueError, match="a decay of q's shape"):
        la._kda_delta_rule(_attrs(chunk=64), q, q, q, q[..., 0], q[..., 0])


def _attrs(**attrs):
    """A rule's context outside a program: its attributes, no lowerer (so
    `note` and `tally` write nothing)."""
    return registry.LoweringContext(attrs)


# -- the gated norm's sigmoid, the router's groups ------------------------------------------

def _sigmoid_norm_want(x, z, w, eps=1e-6):
    return ref.rms_norm(x, w, eps) * jax.nn.sigmoid(z)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_gated_norm_with_a_sigmoid_gate(monkeypatch, kernels):
    """`gated_rms_norm(activation="sigmoid")`: forward, dX, dGate and dScale
    against jnp; the XLA form at a width off the kernels' envelope, the
    kernels under the interpreter on it. And silu is another function."""
    if kernels:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    T, H, D = (32, 2, 128) if kernels else (24, 3, 8)
    assert (db._gated_norm_plan((2, T, H, D), jnp.dtype("float32"))
            == "kernel") == kernels
    rng = np.random.RandomState(5)
    x = rng.randn(2, T, H, D).astype(np.float32)
    z = rng.randn(2, T, H, D).astype(np.float32)
    w = rng.uniform(0.5, 1.5, D).astype(np.float32)

    def build(activation):
        return lambda d: [layers.gated_rms_norm(
            d["x"], d["z"], activation=activation,
            param_attr=fluid.ParamAttr(name="w"))]

    (y,), grads, probe = run_piece(build("sigmoid"), {"x": x, "z": z},
                                   {"w": w})
    assert rel_err(y, _sigmoid_norm_want(x, z, w)) < RTOL
    want = jax.grad(lambda *a: jnp.sum(_sigmoid_norm_want(*a) * probe),
                    (0, 1, 2))(x, z, w)
    for name, g in zip(("x", "z", "w"), want):
        assert rel_err(grads[name], g) < 1e-4, name
    silu = run_piece(build("silu"), {"x": x, "z": z}, {"w": w})[0][0]
    assert rel_err(silu, y) > 0.05


def test_the_gate_is_silu_or_a_norm_first_sigmoid():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data(name="x", shape=[2, 8, 16], dtype="float32",
                        append_batch_size=False)
        with pytest.raises(ValueError, match="silu, or sigmoid"):
            layers.gated_rms_norm(x, x, activation="tanh")
        with pytest.raises(ValueError, match="silu, or sigmoid"):
            layers.gated_rms_norm(x, x, activation="sigmoid",
                                  gate_first=True)
        layers.gated_rms_norm(x, x)
        op = fluid.default_main_program().global_block().ops[-1]
        assert "activation" not in op.attrs     # the program it had


def _route(x, w, b, k, n_group, topk_group):
    def build(d):
        routing = layers.moe_router(
            d["x"], w.shape[1], k, norm_topk_prob=True, score_func="sigmoid",
            norm_eps=1e-20, scaling_factor=2.5, n_group=n_group,
            topk_group=topk_group, param_attr=fluid.ParamAttr(name="w"),
            bias_attr=_planted("b", b))
        return [routing["weight"], routing["index"],
                routing["tokens_per_expert"]]
    return run_piece(build, {"x": x}, {"w": w})


@pytest.mark.parametrize("n_expert,n_group,topk_group,k",
                         [(16, 4, 2, 3), (512, 8, 4, 8), (16, 4, 4, 3)],
                         ids=["tiny", "published", "every_group_stays"])
def test_group_limited_router_against_jnp(n_expert, n_group, topk_group, k):
    """`moe_router(n_group=, topk_group=)`: weights, indices, counts and the
    gradient of the router's weight and input against `top_k`s and masks,
    with a planted non-zero bias; every chosen expert lies in one of the
    token's kept groups, and without groups other experts are chosen."""
    rng = np.random.RandomState(6)
    x = rng.randn(48, 16).astype(np.float32)
    w = rng.randn(16, n_expert).astype(np.float32)
    b = (rng.randn(n_expert) * 0.3).astype(np.float32)
    (weight, index, counts), grads, probe = _route(x, w, b, k, n_group,
                                                   topk_group)
    want_w, want_i = ref.route(x, w, b, k, n_group, topk_group, 2.5)
    assert np.array_equal(index, want_i)
    assert rel_err(weight, want_w) < RTOL
    assert np.array_equal(counts, np.bincount(index.reshape(-1),
                                              minlength=n_expert))
    groups = index // (n_expert // n_group)
    assert max(len(set(row)) for row in groups) <= topk_group
    gx, gw = jax.grad(lambda a, c: jnp.sum(
        ref.route(a, c, b, k, n_group, topk_group, 2.5)[0] * probe),
        (0, 1))(x, w)
    assert rel_err(grads["x"], gx) < 1e-4 and rel_err(grads["w"], gw) < 1e-4
    plain = ref.route(x, w, b, k, n_group, topk_group, 2.5,
                      fault="no_groups")[1]
    assert np.array_equal(index, plain) == (topk_group == n_group)
    if topk_group < n_group:
        best = ref.route(x, w, b, k, n_group, topk_group, 2.5,
                         fault="group_by_best")[1]
        assert not np.array_equal(index, best)


# the router's lowering at Kanana-2's call (4096 rows of 2048 over 128
# experts, sigmoid, a bias, top-6, renormalised and scaled; one group) as the
# parent commit (7e3a880) lowered it: sha256 of the StableHLO text
KANANA2_ROUTER = "aac27c2921abc320ecae4e080e42c84f"


def _router_text(**groups):
    import hashlib
    attrs = dict(k=6, norm_topk_prob=True, score_func="sigmoid",
                 norm_eps=1e-20, scaling_factor=2.448, **groups)
    x = jax.ShapeDtypeStruct((4096, 2048), jnp.float32)
    w = jax.ShapeDtypeStruct((2048, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128,), jnp.float32)
    text = jax.jit(lambda x, w, b: moe._moe_router(
        _attrs(**attrs), x, w, b)).lower(x, w, b).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:32]


def test_a_router_of_one_group_lowers_as_on_the_parent():
    """Kanana-2's call lowers to the text it had before the groups existed,
    and `n_group` 1 is that call: the layer writes no attribute for it."""
    assert _router_text() == KANANA2_ROUTER
    assert _router_text(n_group=1, topk_group=1) == KANANA2_ROUTER
    assert _router_text(n_group=8, topk_group=4) != KANANA2_ROUTER
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data(name="x", shape=[8, 16], dtype="float32",
                        append_batch_size=False)
        layers.moe_router(x, 16, 3, score_func="sigmoid")
        layers.moe_router(x, 16, 3, score_func="sigmoid", n_group=1,
                          topk_group=1)
        ops = [o for o in fluid.default_main_program().global_block().ops
               if o.type == "moe_router"]
        assert ops[0].attrs == ops[1].attrs
        assert "n_group" not in ops[0].attrs
        with pytest.raises(ValueError, match="groups are equal"):
            layers.moe_router(x, 16, 3, n_group=3, topk_group=2)
        with pytest.raises(ValueError, match="hold at least k"):
            layers.moe_router(x, 16, 5, n_group=4, topk_group=1)


# -- the shares add up ---------------------------------------------------------------------

def test_the_shares_add_up_to_the_whole_layer():
    """16 experts in 4 groups of which 2 stay, 4 held a share: the routed
    parts that the four shares give plus the shared expert counted once are
    the uncut reference's whole expert layer: forward, the gradient of the
    router and of the layer's input. With a planted non-zero `b`."""
    d, n_expert, held, k, width = 16, 16, 4, 3, 12
    rng = np.random.RandomState(7)
    f32 = np.float32
    x = rng.randn(40, d).astype(f32)
    whole = {"router.w": rng.randn(d, n_expert),
             "router.bias": rng.randn(n_expert) * 0.3,
             **{f"experts.{n}.w": rng.randn(n_expert, *s) * 0.3
                for n, s in (("gate", (d, width)), ("up", (d, width)),
                             ("down", (width, d)))},
             **{f"shared.{n}.w": rng.randn(*s) * 0.3
                for n, s in (("gate", (d, 20)), ("up", (d, 20)),
                             ("down", (20, d)))}}
    whole = {n: v.astype(f32) for n, v in whole.items()}
    shares = n_expert // held
    cut = {f"s{j}.{which}.w":
           whole[f"experts.{which}.w"][j * held:(j + 1) * held]
           for j in range(shares) for which in ("gate", "up", "down")}

    def build(data):
        routing = layers.moe_router(
            data["x"], n_expert, k, norm_topk_prob=True,
            score_func="sigmoid", norm_eps=1e-20, scaling_factor=2.5,
            n_group=4, topk_group=2,
            param_attr=fluid.ParamAttr(name="router.w"),
            bias_attr=_planted("router.bias", whole["router.bias"]))
        parts = [layers.moe_experts(
            data["x"], routing, n_expert, width, name=f"s{j}",
            experts_held=held, first_expert=j * held) for j in range(shares)]

        def fc(v, size, name):
            return layers.fc(v, size, bias_attr=False,
                             param_attr=fluid.ParamAttr(name=name))

        hidden = layers.swiglu(fc(data["x"], 20, "shared.gate.w"),
                               fc(data["x"], 20, "shared.up.w"))
        return [layers.sums(parts + [fc(hidden, d, "shared.down.w")])] + parts

    params = {**{n: v for n, v in whole.items()
                 if not n.startswith(("experts.", "router.bias"))}, **cut}
    outs, grads, probe = run_piece(build, {"x": x}, params)
    kw = dict(top_k=k, n_group=4, topk_group=2, scale=2.5)

    def want(x, router_w):
        return ref.sparse_experts({**whole, "router.w": router_w}, x,
                                  first_expert=0, **kw)[0]

    with jax.default_matmul_precision("highest"):
        assert rel_err(outs[0], want(x, whole["router.w"])) < RTOL
        gx, gr = jax.grad(lambda a, b: jnp.sum(want(a, b) * probe),
                          (0, 1))(x, whole["router.w"])
        for j in (0, shares - 1):   # a share alone is the reference given it
            own = {n: (v[j * held:(j + 1) * held]
                       if n.startswith("experts.") else v)
                   for n, v in whole.items()}
            alone = ref.sparse_experts(own, x, first_expert=j * held,
                                       fault="no_shared_expert", **kw)[0]
            assert rel_err(outs[1 + j], alone) < 1e-4, j
    assert rel_err(grads["x"], gx) < 1e-4
    assert rel_err(grads["router.w"], gr) < 1e-4


# -- the model -----------------------------------------------------------------------------

def _seeded_values(shapes, seed=3):
    """Weights far from their initial values, so that no term of the
    comparison is small by construction: norm weights in [0.5, 1.5], a router
    five times as sharp, a planted bias of std 0.2, a LIVE decay (`A_log` in
    log [0.5, 2], `dt_bias` in [-2, 2]: g spans (-5, 0)), the other matrices
    of std 0.1 (five times the initial)."""
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
        elif name.endswith("A_log"):
            value = np.log(rng.uniform(0.5, 2, shape))
        elif name.endswith("dt_bias"):
            value = rng.uniform(-2, 2, shape)
        elif name.endswith("conv.w"):
            value = rng.uniform(-0.5, 0.5, shape)
        else:
            value = rng.randn(*shape) * 0.1
        values[name] = value.astype(np.float32)
    return values


FETCHES = ["loss", "ce", "logits", "tokens_per_expert"]
E_LAYERS = [1, 2, 3, 4, 5]
BIASES = [f"l{i}.router.bias" for i in E_LAYERS]
# what each planted fault has to move, at least: the logits or a gradient by
# 1% where the true reference is met within GRAD_TOL
FAULT_WRT = ["l0.kda.q.w", "l0.kda.f.w", "l0.kda.A_log", "l0.kda.dt_bias",
             "l0.kda.b.w", "l0.kda.g.w", "l0.kda.conv.w", "l3.kda.norm.w",
             "l4.mla.kv_a.w", "l4.mla.gate.w", "l1.experts.up.w",
             "l1.shared.down.w", "l1.router.w", "embed.w"]
CASE = DecoderCase(models.ling3.build, TINY, ref, REF_KW, FETCHES,
                   state=BIASES, seeded_values=_seeded_values,
                   fault_wrt=FAULT_WRT, grad_tol=GRAD_TOL)


@pytest.fixture(scope="module")
def tiny():
    made = CASE.tiny_model()
    # the observatory keeps its newest events only: read the step's now
    made["details"] = [
        e.detail for e in observe.observatory().events()
        if e.program_uid == made["main"]._uid
        and "kda_grid_steps" in (getattr(e, "detail", None) or {})]
    return made


KDA = ["kda.q.w", "kda.k.w", "kda.v.w", "kda.f.w", "kda.b.w", "kda.g.w",
       "kda.conv.w", "kda.A_log", "kda.dt_bias", "kda.norm.w", "kda.o.w"]
MLA = ["mla.q.w", "mla.kv_a.w", "mla.kv_norm.w", "mla.kv_b.w", "mla.gate.w",
       "mla.o.w"]
MLP = ["mlp.gate.w", "mlp.up.w", "mlp.down.w"]
MOE = ["router.w"] + [f"{part}.{n}.w" for part in ("experts", "shared")
                      for n in ("gate", "up", "down")]
TRAINED = (["embed.w", "final_norm.w", "head.w"]
           + [f"l{i}.{n}" for i, kind in enumerate(KINDS)
              for n in ["in_norm.w", "post_norm.w"]
              + {"kda": KDA, "mla": MLA}[kind] + (MLP if i == 0 else MOE)])


def test_tiny_model_has_the_reference_parameters(tiny):
    CASE.has_the_reference_parameters(tiny, TRAINED, {
        "l0.kda.q.w": (64, 64), "l0.kda.f.w": (64, 64),
        "l0.kda.g.w": (64, 64), "l0.kda.b.w": (64, 4),
        "l0.kda.conv.w": (3 * 64, 4), "l0.kda.A_log": (4,),
        "l0.kda.dt_bias": (64,), "l0.kda.norm.w": (16,),
        "l4.mla.q.w": (64, 4 * 24), "l4.mla.kv_a.w": (64, 32 + 8),
        "l4.mla.kv_b.w": (32, 4 * 32), "l4.mla.gate.w": (64, 4),
        "l0.mlp.up.w": (64, 96), "l1.experts.up.w": (4, 64, 24),
        "l1.router.w": (64, 16), "l1.shared.up.w": (64, 24)})


def test_the_initial_values_are_the_assumed_ones():
    main, startup, _, _ = CASE.program()
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    value = lambda n: np.asarray(scope.find_var(n))
    a = np.exp(value("l0.kda.A_log"))
    assert np.all(a >= 1) and np.all(a <= 16)
    dt = np.log1p(np.exp(value("l2.kda.dt_bias")))       # softplus
    assert np.all(dt >= 0.001 * 0.999) and np.all(dt <= 0.1 * 1.001)
    assert not np.array_equal(value("l0.kda.A_log"), value("l2.kda.A_log"))
    assert np.abs(value("l0.kda.conv.w")).max() <= 0.5
    assert np.all(value("l0.kda.norm.w") == 1)
    assert np.all(value("l1.router.bias") == 0)
    assert 0.015 < value("l0.kda.q.w").std() < 0.025
    # drawn from the PUBLISHED index: built layer 0 of a run from layer 1 is
    # built layer 1 of a run from layer 0
    other = CASE.program(first_layer=0)[1]
    scope0 = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(other, scope=scope0)
    assert np.array_equal(np.asarray(scope0.find_var("l1.kda.A_log")),
                          value("l0.kda.A_log"))


@pytest.mark.parametrize("name", FETCHES)
def test_tiny_model_output_matches_reference(tiny, name):
    CASE.output_matches_reference(tiny, name)


def test_tiny_routing_sends_most_assignments_elsewhere(tiny):
    CASE.routing_sends_most_assignments_elsewhere(tiny, routed_layers=5)


@pytest.mark.parametrize("name", TRAINED)
def test_tiny_model_gradient_matches_reference(tiny, name):
    CASE.gradient_matches_reference(tiny, name)


@pytest.mark.parametrize("layer", E_LAYERS)
def test_one_step_moves_the_bias_as_next_bias_does(tiny, layer):
    CASE.one_step_moves_the_bias_as_next_bias_does(
        tiny, f"l{layer}.router.bias", GAMMA)


@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_each_planted_fault_is_refused(tiny, fault):
    CASE.planted_fault_is_refused(tiny, fault, factor=10, loss=1e-5)


def test_the_config_names_every_fault_and_no_other():
    assert sorted(CONFIG["reference"]["check"]["faults"]) == sorted(ref.FAULTS)
    assert len(ref.FAULTS) == 18


def test_an_unknown_fault_and_a_wrong_pattern_are_refused(tiny):
    CASE.unknown_fault_is_refused(tiny)
    with pytest.raises(ValueError, match="by the pattern"):
        ref.loss_parts(tiny["params"], tiny["tokens"], tiny["labels"],
                       **{**REF_KW, "first_layer": 0})


def test_reference_in_blocks_is_the_reference(tiny):
    # another order of float32 sums through five rules
    CASE.reference_in_blocks_is_the_reference(
        tiny, ["l0.kda.f.w", "l2.kda.A_log", "l4.mla.kv_a.w", "l3.router.w",
               "embed.w"], tol=1e-4, q_block=32, token_block=16)


def test_reference_last_positions_equal_the_full_pass(tiny):
    CASE.reference_last_positions_equal_the_full_pass(tiny, tol=1e-5)


def test_reference_in_bfloat16_is_another_number(tiny):
    CASE.reference_in_bfloat16_is_another_number(tiny)


def test_the_two_reference_copies_are_one_file():
    CASE.two_copies_of_the_reference_are_identical()


def test_tiny_model_amp_within_bf16_of_reference():
    """Under AMP the residual stream, the projections, the rule's q, k, v,
    attention and the experts are bf16; g, beta, the rule's sums and state,
    the router's scores, `b` and every norm's statistics stay float32. At
    the initial weights (a sharper router flips assignments under bf16
    inputs). A bf16 value carries 8 bits: logits of std ~0.16 here read
    within 0.01 in the mean and 0.12 at most (a token whose assignment
    flipped moves by an expert's whole contribution), the loss within 0.005,
    a gradient within 8% in the Frobenius norm (read: 0.055-0.060 in layer
    0, whose gradients pass back through all six layers' roundings; the
    siblings hold 5%), the rule's q, k and gates within 15%."""
    # q and k reach the loss through the convolution, an l2-norm and both
    # Gram tiles, the gates through a sigmoid that is nearly shut at the
    # initial values
    CASE.amp_within_bf16_of_reference(
        {0.08: ("l0.kda.v.w", "l0.kda.o.w", "l0.kda.g.w", "l4.mla.kv_a.w",
                "l4.mla.gate.w", "l1.shared.up.w", "embed.w", "head.w"),
         0.15: ("l0.kda.q.w", "l0.kda.k.w", "l0.kda.f.w", "l0.kda.dt_bias",
                "l0.kda.b.w")},
        loss=0.005, mean=0.01, most=0.12, of_std=False)


def test_amp_lists_hold_the_gates_and_leave_the_rule_alone():
    assert "kda_gates" in registry.AMP_F32_OPS
    assert "moe_router" in registry.AMP_F32_OPS
    for op in ("kda_delta_rule", "causal_conv1d", "gated_rms_norm",
               "rms_norm"):
        assert op not in registry.AMP_F32_OPS | registry.AMP_BF16_OPS


def test_five_adam_steps_lower_the_loss():
    CASE.adam_steps_lower_the_loss(lr=1e-2, seed=1, steps=5)


@pytest.mark.parametrize("layer", range(6))
def test_every_part_is_built_under_its_own_scope(tiny, layer):
    by_scope = _forward_ops_by_scope(tiny["main"])
    mixer = by_scope[f"l{layer}.{KINDS[layer]}"]
    if KINDS[layer] == "kda":
        assert mixer.count("kda_delta_rule") == mixer.count("kda_gates") == 1
        assert mixer.count("causal_conv1d") == 1
        assert mixer.count("gated_rms_norm") == 1
        assert "fused_attention" not in mixer and "rotary_embedding" \
            not in mixer
        assert f"l{layer}.mla" not in by_scope
    else:
        assert mixer.count("fused_attention") == 1
        assert mixer.count("rotary_embedding") == 2
        assert mixer.count("sigmoid") == 1
        assert "kda_delta_rule" not in mixer
    fed = by_scope[f"l{layer}.mlp" if layer == 0 else f"l{layer}.moe"]
    assert ("moe_router" in fed) == (layer != 0)
    assert fed.count("swiglu") == (1 if layer == 0 else 2)


def test_layer_census_reads_the_issues_counts():
    """5 KDA layers, 1 latent-attention layer with a head gate, 1 dense and
    5 expert layers with (at the published counts) 512 routed, 8 held,
    sigmoid scores in 8 groups of which 4 stay, 5 bias updates."""
    sizes = dict(n_expert=512, top_k=8, n_group=8, topk_group=4,
                 experts_held=8, first_expert=0)
    main, _, _, _ = CASE.program(fluid.optimizer.Adam(1e-3), **sizes)
    got = census.layer_census(main)
    assert got["layer_kinds"] == {"kda": 5, "latent_attention": 1}
    assert got["kda_layers"] == 5
    assert got["latent_attention_gated_layers"] == 1
    assert got["attention_gated_layers"] == 1
    assert (got["attention_qk_width"], got["attention_value_width"]) \
        == (24, 16)
    assert got["dense_ffn_layers"] == 1
    assert got["moe_experts_routed"] == 512
    assert got["moe_experts_held"] == 8
    assert got["moe_router_score"] == "sigmoid"
    assert (got["moe_router_groups"], got["moe_router_groups_kept"]) == (8, 4)
    assert got["moe_router_bias_updates"] == 5
    # and a program of one group reports none
    other = models.kanana2
    main2 = fluid.Program()
    with fluid.program_guard(main2, fluid.Program()), \
            fluid.unique_name.guard():
        other.build(vocab_size=64, seq_len=32, n_layer=2, d_model=32,
                    d_dense=48, n_head=2, kv_rank=16, qk_nope_dim=8,
                    qk_rope_dim=8, v_head_dim=8, n_expert=8, top_k=2,
                    d_expert=16)
    plain = census.layer_census(main2)
    assert "moe_router_groups" not in plain and "kda_layers" not in plain
    assert "latent_attention_gated_layers" not in plain


def test_the_compile_event_counts_the_rules_chunk_steps(tiny):
    """`kda_plan` and `kda_grid_steps` on the main program's compile event:
    5 layers x (the op and its grad op) x batch 2 x 4 heads x 4 chunks. Heads
    of 16 fill no vreg, so the rule keeps the XLA form here and no kernel
    grid step is tallied (`tests/test_kda_kernels.py` holds `"kernel"` and
    `kda_kernel_grid_steps` at heads of 128)."""
    assert tiny["details"]
    detail = tiny["details"][-1]
    assert detail["kda_plan"] == "xla"
    assert "kda_kernel_grid_steps" not in detail
    assert detail["kda_grid_steps"] == 5 * 2 * 2 * 4 * 4
    assert detail["kda_layers"] == 5
