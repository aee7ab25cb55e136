"""Qwen3-Next (gated-delta-rule layers beside gated softmax attention, one
chip's share of a renormalised top-k expert layer with a shared expert)
through `layers` -> Program IR -> `Executor`, against the plain reference
(`tests/qwen3_next_reference.py`: the delta rule as its token-by-token
recurrence, a loop over the held experts). Seeded random weights,
float32, AMP off unless a test says otherwise."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models, observe
from paddle_tpu.ops import linear_attention as la
from paddle_tpu.ops import moe

import qwen3_next_reference as ref
from decoder_case import (DIGESTS, REGIMES, RTOL, DecoderCase, build_program,
                          carries_the_census, frob,
                          layers_are_built_under_their_scopes, program_digest,
                          rel_err, run_piece, tiny_args)

TINY = tiny_args("qwen3_next")
REF_KW = {k: TINY[k] for k in (
    "n_layer", "n_head", "n_kv_head", "head_dim", "rotary_dim", "rope_theta",
    "full_attention_interval", "n_key_head", "n_value_head", "key_dim",
    "value_dim", "top_k", "first_expert")}


# -- the delta rule: chunks against the recurrence ----------------------------

def _rule_inputs(t, regime, heads=3, dk=8, dv=6, seed=0):
    rng = np.random.RandomState(seed)
    (gs, go), (bs, bo) = REGIMES[regime]
    q = rng.randn(2, t, heads, dk).astype(np.float32)
    k = rng.randn(2, t, heads, dk).astype(np.float32)
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.randn(2, t, heads, dv).astype(np.float32)
    a = rng.randn(2, t, heads).astype(np.float32) * gs + go
    g = -np.exp(rng.uniform(-1, 2.5, heads)).astype(np.float32) \
        * np.log1p(np.exp(a))
    beta = 1 / (1 + np.exp(-(rng.randn(2, t, heads) * bs + bo)))
    return q, k, v, g.astype(np.float32), beta.astype(np.float32)


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("t,chunk", [(128, 128), (128, 64), (256, 64),
                                     (512, 64), (1024, 64), (384, 32)])
def test_chunked_rule_is_the_recurrence(t, chunk, regime):
    """Forward and the gradient of every input, one chunk to sixteen."""
    args = _rule_inputs(t, regime)
    probe = np.random.RandomState(9).randn(2, t, 3, 6).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(
            lambda *a: jnp.sum(ref.delta_rule(*a, token_block=64) * probe),
            argnums=(0, 1, 2, 3, 4))(*args)
        got, got_grads = jax.value_and_grad(
            lambda *a: jnp.sum(la.chunked_gated_delta_rule(*a, chunk)
                               * probe), argnums=(0, 1, 2, 3, 4))(*args)
        out = la.chunked_gated_delta_rule(*args, chunk)
    assert np.all(np.isfinite(out))
    assert frob(out, ref.delta_rule(*args)) < RTOL
    assert abs(float(got) - float(want)) <= 1e-4 * (1 + abs(float(want)))
    for name, g, w in zip("q k v g beta".split(), got_grads, want_grads):
        assert np.all(np.isfinite(g)), name
        assert frob(g, w) < 2e-4, (name, frob(g, w))


def test_rule_layer_matches_reference_with_grouped_heads():
    """`layers.gated_delta_rule`: the gates from a, b, A_log and dt_bias,
    the l2-norms and the scale inside the op, a key head serving two value
    heads; forward and the gradient of every input and parameter."""
    rng = np.random.RandomState(2)
    b, t, hk, hv, dk, dv = 2, 128, 2, 4, 8, 8
    feed = {"q": rng.randn(b, t, hk, dk), "k": rng.randn(b, t, hk, dk),
            "v": rng.randn(b, t, hv, dv), "a": rng.randn(b, t, hv),
            "b": rng.randn(b, t, hv)}
    feed = {n: x.astype(np.float32) for n, x in feed.items()}
    params = {"A_log": np.log(rng.uniform(0.1, 4, hv)).astype(np.float32),
              "dt_bias": rng.uniform(0.5, 1.5, hv).astype(np.float32)}
    (y,), grads, probe = run_piece(
        lambda d: [layers.gated_delta_rule(
            d["q"], d["k"], d["v"], d["a"], d["b"],
            a_log_attr=fluid.ParamAttr(name="A_log"),
            dt_bias_attr=fluid.ParamAttr(name="dt_bias"))], feed, params)

    def want(q, k, v, a, b_in, a_log, dt_bias):
        g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * dk ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        return ref.delta_rule(jnp.repeat(q, 2, 2), jnp.repeat(k, 2, 2), v, g,
                              jax.nn.sigmoid(b_in))

    args = [feed[n] for n in "qkvab"] + [params["A_log"], params["dt_bias"]]
    with jax.default_matmul_precision("highest"):
        assert frob(y, want(*args)) < RTOL
        want_grads = jax.grad(lambda *a: jnp.sum(want(*a) * probe),
                              argnums=tuple(range(7)))(*args)
    for name, w in zip(list("qkvab") + ["A_log", "dt_bias"], want_grads):
        assert frob(grads[name], w) < 2e-4, name


def test_rule_refuses_a_length_that_is_not_whole_chunks():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = layers.data(name="q", shape=[1, 96, 2, 8], dtype="float32",
                        append_batch_size=False)
        a = layers.data(name="a", shape=[1, 96, 2], dtype="float32",
                        append_batch_size=False)
        out = layers.gated_delta_rule(q, q, q, a, a)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    with pytest.raises(Exception, match="multiple of the chunk"):
        exe.run(main, feed={"q": np.zeros((1, 96, 2, 8), np.float32),
                            "a": np.zeros((1, 96, 2), np.float32)},
                fetch_list=[out], scope=scope)


# -- the small ops --------------------------------------------------------------

def test_causal_conv_reads_no_later_input():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 32, 6).astype(np.float32)
    w = rng.uniform(-0.5, 0.5, (6, 4)).astype(np.float32)

    def run(x):
        (y,), grads, probe = run_piece(
            lambda d: [layers.causal_conv1d(
                d["x"], 4, param_attr=fluid.ParamAttr(name="w"))],
            {"x": x}, {"w": w})
        return y, grads, probe

    y, grads, probe = run(x)
    assert rel_err(y, ref.causal_conv_silu(x, w)) < RTOL
    gx, gw = jax.grad(lambda a, b: jnp.sum(ref.causal_conv_silu(a, b)
                                           * probe), (0, 1))(x, w)
    assert rel_err(grads["x"], gx) < RTOL and rel_err(grads["w"], gw) < RTOL
    later = x.copy()
    later[:, 17:] += rng.randn(2, 15, 6)        # inputs 17.. change
    moved, _, _ = run(later)
    assert np.array_equal(moved[:, :17], y[:, :17])     # outputs ..16 do not
    assert not np.allclose(moved[:, 17], y[:, 17])


def test_zero_centred_rms_norm_matches_reference():
    rng = np.random.RandomState(0)
    x = rng.randn(3, 5, 16).astype(np.float32)
    w = rng.uniform(-0.5, 0.5, 16).astype(np.float32)
    (y,), grads, probe = run_piece(
        lambda d: [layers.rms_norm(d["x"], epsilon=1e-6, zero_centered=True,
                                   param_attr=fluid.ParamAttr(name="w"))],
        {"x": x}, {"w": w})
    gx, gw = jax.grad(lambda a, b: jnp.sum(ref.rms_norm(a, b, 1e-6) * probe),
                      (0, 1))(x, w)
    assert rel_err(y, ref.rms_norm(x, w, 1e-6)) < RTOL
    assert rel_err(grads["x"], gx) < RTOL and rel_err(grads["w"], gw) < RTOL


def test_zero_centred_weight_starts_at_zero_and_plain_at_one():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[2, 8], dtype="float32",
                        append_batch_size=False)
        layers.rms_norm(x, zero_centered=True,
                        param_attr=fluid.ParamAttr(name="zc"))
        layers.rms_norm(x, param_attr=fluid.ParamAttr(name="plain"))
        layers.gated_rms_norm(x, x, param_attr=fluid.ParamAttr(name="gated"))
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    assert not np.any(np.asarray(scope.find_var("zc")))
    assert np.all(np.asarray(scope.find_var("plain")) == 1)
    assert np.all(np.asarray(scope.find_var("gated")) == 1)


def test_gated_rms_norm_matches_reference():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 7, 4, 8).astype(np.float32)
    z = rng.randn(2, 7, 4, 8).astype(np.float32)
    w = rng.uniform(0.5, 1.5, 8).astype(np.float32)

    def want(x, z, w):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-6) * w * jax.nn.silu(z)

    (y,), grads, probe = run_piece(
        lambda d: [layers.gated_rms_norm(
            d["x"], d["z"], param_attr=fluid.ParamAttr(name="w"))],
        {"x": x, "z": z}, {"w": w})
    gx, gz, gw = jax.grad(lambda *a: jnp.sum(want(*a) * probe),
                          (0, 1, 2))(x, z, w)
    assert rel_err(y, want(x, z, w)) < RTOL
    for name, g in (("x", gx), ("z", gz), ("w", gw)):
        assert rel_err(grads[name], g) < RTOL, name


def test_partial_rotary_turns_the_first_dims_only():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 3, 16, 12).astype(np.float32)
    (y,), grads, probe = run_piece(
        lambda d: [layers.rotary_embedding(d["x"], theta=100.0,
                                           rotary_dim=4)], {"x": x})
    assert rel_err(y, ref.rotary(x, 100.0, 4)) < RTOL
    assert np.array_equal(y[..., 4:], x[..., 4:])
    assert not np.allclose(y[:, :, 1:, :4], x[:, :, 1:, :4])
    gx = jax.grad(lambda a: jnp.sum(ref.rotary(a, 100.0, 4) * probe))(x)
    assert rel_err(grads["x"], gx) < RTOL
    whole, _, _ = run_piece(
        lambda d: [layers.rotary_embedding(d["x"], theta=100.0)], {"x": x})
    assert rel_err(whole[0], ref.rotary(x, 100.0, 12)) < RTOL


def test_router_renormalises_over_all_chosen_experts():
    rng = np.random.RandomState(0)
    x = rng.randn(24, 8).astype(np.float32)
    w = rng.randn(8, 16).astype(np.float32)

    def build(norm):
        return lambda d: [layers.moe_router(
            d["x"], 16, 4, param_attr=fluid.ParamAttr(name="w"),
            norm_topk_prob=norm)["weight"]]

    (y,), grads, probe = run_piece(build(True), {"x": x}, {"w": w})
    (plain,), _, _ = run_piece(build(False), {"x": x}, {"w": w})
    assert np.allclose(y.sum(-1), 1, atol=1e-6)
    assert np.all(plain.sum(-1) < 0.999)
    assert rel_err(y, plain / plain.sum(-1, keepdims=True)) < RTOL

    def want(x, w):
        probs = jax.nn.softmax(x @ w, axis=-1)
        top, _ = jax.lax.top_k(probs, 4)
        return top / jnp.sum(top, -1, keepdims=True)

    with jax.default_matmul_precision("highest"):
        gx, gw = jax.grad(lambda a, b: jnp.sum(want(a, b) * probe),
                          (0, 1))(x, w)
    assert rel_err(grads["x"], gx) < 1e-4 and rel_err(grads["w"], gw) < 1e-4


# -- one chip's share of the expert layer -----------------------------------------

N_EXPERT, HELD, K, D, F = 16, 4, 4, 16, 12


def _expert_weights(rng, shares=N_EXPERT // HELD):
    """The whole layer's weights under the reference's names, and the same
    cut into the shares' stacks `s<j>.{gate,up,down}.w`."""
    whole = {"router.w": rng.randn(D, N_EXPERT).astype(np.float32),
             "experts.gate.w": rng.randn(N_EXPERT, D, F) * 0.3,
             "experts.up.w": rng.randn(N_EXPERT, D, F) * 0.3,
             "experts.down.w": rng.randn(N_EXPERT, F, D) * 0.3,
             "shared.gate.w": rng.randn(D, F) * 0.3,
             "shared.up.w": rng.randn(D, F) * 0.3,
             "shared.down.w": rng.randn(F, D) * 0.3,
             "shared_gate.w": rng.randn(D, 1)}
    whole = {n: v.astype(np.float32) for n, v in whole.items()}
    cut = {f"s{j}.{which}.w":
           whole[f"experts.{which}.w"][j * HELD:(j + 1) * HELD]
           for j in range(shares) for which in ("gate", "up", "down")}
    return whole, cut


@pytest.mark.parametrize("path", ["ragged_dot", "pallas_interpreted"])
def test_the_shares_add_up_to_the_whole_layer(path, monkeypatch):
    """The routed parts that all four shares give, plus the shared expert
    once, are the uncut reference's whole layer: forward, the gradient of
    the router and of the layer's input."""
    if path == "pallas_interpreted":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(5)
    x = rng.randn(40, D).astype(np.float32)
    whole, cut = _expert_weights(rng)

    def build(d):
        routing = layers.moe_router(
            d["x"], N_EXPERT, K, norm_topk_prob=True,
            param_attr=fluid.ParamAttr(name="router.w"))
        parts = [layers.moe_experts(
            d["x"], routing, N_EXPERT, F, name=f"s{j}",
            first_expert=j * HELD, experts_held=HELD)
            for j in range(N_EXPERT // HELD)]

        def fc(v, size, name):
            return layers.fc(v, size, bias_attr=False,
                             param_attr=fluid.ParamAttr(name=name))

        hidden = layers.swiglu(fc(d["x"], F, "shared.gate.w"),
                               fc(d["x"], F, "shared.up.w"))
        shared = layers.elementwise_mul(
            fc(hidden, D, "shared.down.w"),
            layers.sigmoid(fc(d["x"], 1, "shared_gate.w")))
        return [layers.sums(parts + [shared])] + parts

    params = {**{n: v for n, v in whole.items()
                 if not n.startswith("experts.")}, **cut}
    outs, grads, probe = run_piece(build, {"x": x}, params)

    def want(x, router_w):
        out, _, _ = ref.sparse_experts({**whole, "router.w": router_w}, x,
                                       top_k=K, first_expert=0)
        return out

    with jax.default_matmul_precision("highest"):
        assert rel_err(outs[0], want(x, whole["router.w"])) < RTOL
        gx, gr = jax.grad(lambda a, b: jnp.sum(want(a, b) * probe),
                          (0, 1))(x, whole["router.w"])
        # and each share alone is the reference given that share
        for j, part in enumerate(outs[1:]):
            held = {n: (v[j * HELD:(j + 1) * HELD]
                        if n.startswith("experts.") else v)
                    for n, v in whole.items()}
            alone, _, _ = ref.sparse_experts(held, x, top_k=K,
                                             first_expert=j * HELD)
            shared, _, _ = ref.sparse_experts(
                {**held, **{n: v[:0] for n, v in held.items()
                            if n.startswith("experts.")}}, x, top_k=K,
                first_expert=0)
            assert rel_err(part, alone - shared) < 1e-4, j
    assert rel_err(grads["x"], gx) < 1e-4
    assert rel_err(grads["router.w"], gr) < 1e-4


def _share_on_given_routing(index, weight, x, weights, first):
    n_expert = 16
    counts = np.bincount(index.reshape(-1), minlength=n_expert) \
        .astype(np.int32)

    def build(d):
        routing = {"weight": d["weight"], "index": d["index"],
                   "tokens_per_expert": d["counts"]}
        return [layers.moe_experts(d["x"], routing, n_expert, F, name="e",
                                   first_expert=first, experts_held=HELD)]

    return run_piece(build, {"x": x, "weight": weight, "index": index,
                             "counts": counts}, weights)


def _uneven(rng, n):
    index = np.tile(np.arange(8, 12, dtype=np.int32), (n, 1))
    index[:n // 2, 3] = 9      # groups of n, 1.5 n, n, 0.5 n
    return np.stack([rng.permutation(r) for r in index])


@pytest.mark.parametrize("routing,n,groups", [
    (_uneven, 160, [160, 240, 160, 80]),
    # the worst case of the worst case: every choice on one held expert
    (lambda rng, n: np.full((n, 4), 10, np.int32), 160, [0, 0, 640, 0]),
    # groups that end on a tile: no padding row anywhere
    (lambda rng, n: np.stack([rng.permutation(4) + 8 for _ in range(n)]),
     128, [128, 128, 128, 128]),
], ids=["uneven", "one_expert", "whole_tiles"])
def test_every_token_on_held_experts_is_exact(routing, n, groups):
    """Adversarial routings: all 4 choices of all tokens fall on the 4 held
    experts (n x 4 assignments; the layout has n x 4 + 4 x 128 rows, the
    worst case, which they fit whatever the groups). Every assignment is
    computed: result and gradients are the loop's over the held experts."""
    rng = np.random.RandomState(6)
    x = rng.randn(n, D).astype(np.float32)
    index = routing(rng, n).astype(np.int32)
    assert np.bincount(index.reshape(-1) - 8, minlength=4).tolist() == groups
    weight = rng.uniform(0.05, 0.4, (n, K)).astype(np.float32)
    weights = {"e.gate.w": rng.randn(HELD, D, F).astype(np.float32) * .3,
               "e.up.w": rng.randn(HELD, D, F).astype(np.float32) * .3,
               "e.down.w": rng.randn(HELD, F, D).astype(np.float32) * .3}
    (y,), grads, probe = _share_on_given_routing(index, weight, x, weights,
                                                 8)
    p = {"experts." + k.split(".", 1)[1]: v for k, v in weights.items()}

    def want(x, weight, p):
        out = jnp.zeros_like(x)
        for e in range(HELD):
            mask = jnp.sum(jnp.where(index == 8 + e, weight, 0), -1,
                           keepdims=True)
            hidden = jax.nn.silu(x @ p["experts.gate.w"][e]) \
                * (x @ p["experts.up.w"][e])
            out = out + mask * (hidden @ p["experts.down.w"][e])
        return out

    with jax.default_matmul_precision("highest"):
        assert rel_err(y, want(x, weight, p)) < RTOL
        gx, gweight, gp = jax.grad(
            lambda a, b, c: jnp.sum(want(a, b, c) * probe), (0, 1, 2))(
                x, weight, p)
    assert rel_err(grads["x"], gx) < RTOL
    assert rel_err(grads["weight"], gweight) < RTOL
    for name in weights:
        assert rel_err(grads[name],
                       gp["experts." + name.split(".", 1)[1]]) < RTOL, name


def test_assignments_to_absent_experts_give_nothing_either_way():
    """No choice falls on a held expert: the share's part is exactly zero,
    and so are the gradients of its input, its router weights and its
    expert weights."""
    rng = np.random.RandomState(7)
    x = rng.randn(32, D).astype(np.float32)
    index = np.tile(np.array([0, 1, 2, 12], np.int32), (32, 1))
    weight = rng.uniform(0.05, 0.4, (32, K)).astype(np.float32)
    weights = {"e.gate.w": rng.randn(HELD, D, F).astype(np.float32),
               "e.up.w": rng.randn(HELD, D, F).astype(np.float32),
               "e.down.w": rng.randn(HELD, F, D).astype(np.float32)}
    (y,), grads, _ = _share_on_given_routing(index, weight, x, weights, 4)
    assert not np.any(y)
    for name, g in grads.items():
        assert not np.any(g), name


def test_share_layout_follows_the_held_assignments():
    """Group sizes are whole tiles of the held experts' rows and sum to less
    than the rows there are; every held assignment has its slot and the
    others have none."""
    rng = np.random.RandomState(8)
    n = 96
    index = np.stack([rng.choice(16, 4, replace=False) for _ in range(n)]) \
        .astype(np.int32)
    counts = np.bincount(index.reshape(-1), minlength=16).astype(np.int32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        d = {name: layers.data(name=name, shape=list(v.shape),
                               dtype=str(v.dtype), append_batch_size=False)
             for name, v in (("x", np.zeros((n, D), np.float32)),
                             ("index", index), ("counts", counts),
                             ("weight", np.zeros((n, 4), np.float32)))}
        layers.moe_experts(d["x"], {"weight": d["weight"],
                                    "index": d["index"],
                                    "tokens_per_expert": d["counts"]},
                           16, F, name="e", first_expert=4, experts_held=4)
    op = next(o for o in main.global_block().ops if o.type == "moe_dispatch")
    assert op.attrs["first_expert"] == 4 and op.attrs["experts_held"] == 4
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    x = rng.randn(n, D).astype(np.float32)
    sizes, slot, source, rows = exe.run(
        main, feed={"x": x, "index": index, "counts": counts,
                    "weight": np.zeros((n, 4), np.float32)},
        fetch_list=[op.output(s)[0] for s in
                    ("GroupSizes", "Slot", "Source", "XSorted")],
        scope=scope)
    assert rows.shape[0] == n * 4 + 4 * 128         # the worst case
    assert list(sizes) == [-(-c // 128) * 128 for c in counts[4:8]]
    assert sizes.sum() < rows.shape[0]
    held = (index.reshape(-1) >= 4) & (index.reshape(-1) < 8)
    assert np.array_equal(slot >= 0, held)
    assert np.array_equal(np.sort(source[source >= 0]), np.flatnonzero(held))
    assert np.array_equal(rows[slot[held]], x[np.flatnonzero(held) // 4])
    assert np.all(source[sizes.sum():] == -1)


def _held_weights(rng):
    return {"e.gate.w": rng.randn(HELD, D, F).astype(np.float32) * .3,
            "e.up.w": rng.randn(HELD, D, F).astype(np.float32) * .3,
            "e.down.w": rng.randn(HELD, F, D).astype(np.float32) * .3}


def _on_a_chunks_edge(rng, n, groups):
    """n tokens, each on the four held experts 8..11 once, then one choice
    of token 0 bent away from every group that is to be one short: to
    expert 11 if that is to be one over, else to an expert held elsewhere."""
    index = np.stack([rng.permutation(4) + 8 for _ in range(n)])
    for expert, count in zip(range(8, 12), groups):
        if count < n:
            index[0, list(index[0]).index(expert)] = 11 if n + 1 in groups \
                else 0
    return index.astype(np.int32)


@pytest.mark.parametrize("groups,used", [
    ([128, 128, 128, 127], 512),    # one row short of the first chunk's end
    ([128, 128, 128, 128], 512),    # exactly on it
    ([127, 128, 128, 129], 640),    # one row into the next chunk
], ids=["one_short", "on_the_edge", "one_over"])
def test_held_rows_that_end_at_a_chunks_edge_are_all_moved(groups, used):
    """The movements go over the used rows a chunk at a time
    (`ops/moe.py::_over_used_rows`): 512 rows a chunk here, and the held
    rows end at row 510, at row 511 and at row 512 of the layout. Result
    and gradients are the loop's over the held experts."""
    n = 128
    assert math.gcd(n * K + HELD * moe.ROW_TILE, moe._MOVE_ROWS) == 512
    rng = np.random.RandomState(11)
    x = rng.randn(n, D).astype(np.float32)
    index = _on_a_chunks_edge(rng, n, groups)
    assert [int(np.sum(index == e)) for e in range(8, 12)] == groups
    assert sum(-(-g // 128) * 128 for g in groups) == used
    weight = rng.uniform(0.05, 0.4, (n, K)).astype(np.float32)
    weights = _held_weights(rng)
    (y,), grads, probe = _share_on_given_routing(index, weight, x, weights,
                                                 8)

    def want(x, weight, p):
        out = jnp.zeros_like(x)
        for e in range(HELD):
            mask = jnp.sum(jnp.where(index == 8 + e, weight, 0), -1,
                           keepdims=True)
            hidden = jax.nn.silu(x @ p["e.gate.w"][e]) * (x @ p["e.up.w"][e])
            out = out + mask * (hidden @ p["e.down.w"][e])
        return out

    with jax.default_matmul_precision("highest"):
        assert rel_err(y, want(x, weight, weights)) < RTOL
        gx, gweight, gp = jax.grad(
            lambda a, b, c: jnp.sum(want(a, b, c) * probe), (0, 1, 2))(
                x, weight, weights)
    assert rel_err(grads["x"], gx) < RTOL
    assert rel_err(grads["weight"], gweight) < RTOL
    for name in weights:
        assert rel_err(grads[name], gp[name]) < RTOL, name


def test_two_runs_of_one_step_give_the_same_bits():
    """The token-side sums add a token's rows in the layout's order, expert
    by expert: nothing is accumulated in an order that a run chooses."""
    rng = np.random.RandomState(12)
    n = 160
    x = rng.randn(n, D).astype(np.float32)
    index = _uneven(rng, n).astype(np.int32)
    index[::3, 0] = 2               # some choices on experts held elsewhere
    weight = rng.uniform(0.05, 0.4, (n, K)).astype(np.float32)
    weights = _held_weights(rng)
    runs = [_share_on_given_routing(index, weight, x, weights, 8)
            for _ in range(2)]
    assert np.array_equal(runs[0][0][0], runs[1][0][0])
    assert np.any(runs[0][0][0])
    for name, g in runs[0][1].items():
        assert np.array_equal(g, runs[1][1][name]), name


def _share_movements(n, index, feed):
    """`moe_dispatch` and `moe_combine` of a share with nothing between
    them: the combine reads a fed `y` in the layout, the dispatch's rows
    meet a fed probe `p_rows`, so a test chooses what lies in the rows that
    no held group uses. Returns Out, the gradients of x, y and weight, and
    the used rows."""
    from paddle_tpu.layer_helper import LayerHelper
    rows = n * K + HELD * 128
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        d = {name: layers.data(name=name, shape=shape, dtype=dtype,
                               append_batch_size=False,
                               stop_gradient=dtype != "float32")
             for name, shape, dtype in (
                 ("x", [n, D], "float32"), ("weight", [n, K], "float32"),
                 ("y", [rows, D], "float32"), ("index", [n, K], "int32"),
                 ("counts", [16], "int32"), ("p_rows", [rows, D], "float32"),
                 ("p_out", [n, D], "float32"))}
        helper = LayerHelper("share_movements")
        new = helper.create_variable_for_type_inference
        x_sorted, out = new("float32"), new("float32")
        slot, source, sizes = (new("int32", stop_gradient=True)
                               for _ in range(3))
        share = {"first_expert": 8, "experts_held": HELD}
        helper.append_op(
            "moe_dispatch",
            inputs={"X": [d["x"].name], "TopKIndex": [d["index"].name],
                    "TokensPerExpert": [d["counts"].name]},
            outputs={"XSorted": [x_sorted.name], "Slot": [slot.name],
                     "Source": [source.name], "GroupSizes": [sizes.name]},
            attrs={"row_tile": 128, **share})
        helper.append_op(
            "moe_combine",
            inputs={"Y": [d["y"].name], "TopKWeight": [d["weight"].name],
                    "Slot": [slot.name], "Source": [source.name],
                    "GroupSizes": [sizes.name]},
            outputs={"Out": [out.name]}, attrs=share)
        loss = layers.elementwise_add(
            layers.reduce_sum(layers.elementwise_mul(x_sorted, d["p_rows"])),
            layers.reduce_sum(layers.elementwise_mul(out, d["p_out"])))
        fluid.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    counts = np.bincount(index.reshape(-1), minlength=16).astype(np.int32)
    got = exe.run(main, feed={**feed, "index": index, "counts": counts},
                  fetch_list=[out.name, "x@GRAD", "y@GRAD", "weight@GRAD",
                              sizes.name], scope=scope)
    return got[:4], int(np.sum(got[4]))


def test_rows_that_no_held_group_uses_are_never_read():
    """NaN in every row of `Y` and of `dXSorted` behind the used tiles: the
    output and every gradient are finite, and bit for bit the clean run's.
    (Nothing multiplies those rows by a zero; no movement visits them.)"""
    rng = np.random.RandomState(13)
    n = 96
    rows = n * K + HELD * 128
    index = np.stack([rng.choice(16, K, replace=False) for _ in range(n)]) \
        .astype(np.int32)
    feed = {"x": rng.randn(n, D).astype(np.float32),
            "weight": rng.uniform(0.05, 0.4, (n, K)).astype(np.float32),
            "y": rng.randn(rows, D).astype(np.float32),
            "p_rows": rng.randn(rows, D).astype(np.float32),
            "p_out": rng.randn(n, D).astype(np.float32)}
    clean, used = _share_movements(n, index, feed)
    assert 0 < used < rows - 128
    poisoned = dict(feed, y=feed["y"].copy(), p_rows=feed["p_rows"].copy())
    poisoned["y"][used:] = np.nan
    poisoned["p_rows"][used:] = np.nan
    dirty, _ = _share_movements(n, index, poisoned)
    for name, a, b in zip(("out", "d_x", "d_y", "d_weight"), clean, dirty):
        assert np.all(np.isfinite(b)), name
        assert np.array_equal(a, b), name
        assert np.any(a), name
    # a weight's gradient where its assignment has a row, and only there
    held = (index >= 8) & (index < 12)
    assert np.array_equal(clean[3] != 0, held)


# -- between dispatch and combine: the elementwise passes of a share -----------------

# (top_k, experts_held, expert width, model width) of the three cells that
# hold a share, the widths a sixteenth of theirs, and as many tokens as make
# the layout's rows a whole number of elementwise chunks
SHARE_CELLS = {"qwen3_next": (10, 32, 32, 128, 512),
               "mellum2": (8, 8, 56, 144, 384),
               "kanana2": (6, 16, 48, 128, 1024)}


def _layout(cell):
    """(rows of the cell's layout at its small size, the elementwise chunk)."""
    k, held, _, _, n = SHARE_CELLS[cell]
    rows = n * k + held * moe.ROW_TILE
    chunk = math.gcd(rows, moe._ELEMENTWISE_ROWS)
    assert chunk >= 1024 and rows >= 2 * chunk
    return rows, chunk


def _sizes_using(held, used):
    """`held` group sizes, whole tiles, uneven, that sum to `used`."""
    sizes = np.zeros(held, np.int32)
    for tile in range(used // moe.ROW_TILE):
        sizes[(tile * tile) % held] += moe.ROW_TILE
    assert sizes.sum() == used
    return sizes


def _run_ops(build, feed):
    """`build(data)` -> (a result whose sum times `probe` is the loss, the
    names to fetch); every value of `feed` a data variable, the float ones
    with a gradient."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        data = {name: layers.data(name=name, shape=list(v.shape),
                                  dtype=str(v.dtype), append_batch_size=False,
                                  stop_gradient=v.dtype.kind == "i")
                for name, v in feed.items()}
        result, fetch = build(data)
        loss = layers.reduce_sum(layers.elementwise_mul(result,
                                                        data["probe"]))
        fluid.append_backward(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    got = exe.run(main, feed=feed, fetch_list=[result.name] + fetch,
                  scope=scope)
    return [np.asarray(v) for v in got], main


def _silu_product_piece(feed, bounded):
    def build(d):
        out = layers.swiglu(d["gate"], d["up"],
                            group_sizes=d["sizes"] if bounded else None)
        return out, ["gate@GRAD", "up@GRAD"]
    return _run_ops(build, feed)[0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("where", ["inside", "on_the_edge", "one_tile_over",
                                   "none"])
@pytest.mark.parametrize("cell", sorted(SHARE_CELLS))
def test_bounded_silu_product_is_the_static_one_on_the_used_rows(
        cell, where, dtype, monkeypatch):
    """`swiglu` with `GroupSizes` and its registered grad against the static
    product under the generic vjp grad op (the parent's pair): the used
    rows' Out, dGate and dUp bit for bit, with NaN in every row behind
    them; where the used rows end one tile inside a chunk, exactly at its
    edge and one tile over it, and where there are none."""
    from paddle_tpu.core import registry
    _, held, width, _, _ = SHARE_CELLS[cell]
    rows, chunk = _layout(cell)
    used = {"inside": chunk - moe.ROW_TILE, "on_the_edge": chunk,
            "one_tile_over": chunk + moe.ROW_TILE, "none": 0}[where]
    rng = np.random.RandomState(14)
    as_dtype = jnp.dtype(dtype)
    feed = {name: np.asarray(rng.randn(rows, width) * 2, as_dtype)
            for name in ("gate", "up", "probe")}
    feed["sizes"] = _sizes_using(held, used)
    with monkeypatch.context() as patch:
        patch.setattr(registry.get_op_def("swiglu"), "grad_lower", None)
        static = _silu_product_piece(feed, bounded=False)
    registered = _silu_product_piece(feed, bounded=False)
    poisoned = {name: v.copy() for name, v in feed.items()}
    for name in ("gate", "up", "probe"):
        poisoned[name][used:] = np.nan
    bounded = _silu_product_piece(poisoned, bounded=True)
    for name, want, whole, got in zip(("out", "d_gate", "d_up"), static,
                                      registered, bounded):
        assert want.dtype == got.dtype == as_dtype, name
        # without `GroupSizes` the registered grad is the generic one
        assert np.array_equal(want, whole), name
        assert np.array_equal(want[:used], got[:used]), name
        assert np.all(np.isfinite(got[:used].astype(np.float32))), name
        assert used == 0 or np.any(got[:used]), name
        # behind the last chunk nothing was visited: the product is an
        # allocation (zeros on the CPU), the two gradients are written over
        # gate and up, whose rows there stay the NaN they were
        last = -(-used // chunk) * chunk
        if name == "out":
            assert not np.any(got[last:]), name
        else:
            assert np.all(np.isnan(got[last:].astype(np.float32))), name


def _projections_piece(feed, joined):
    """The gate's and the up projection's `grouped_matmul` on fed rows, as
    one op of two weights (`joined`) or as the two ops whose input gradients
    `append_backward` sums; the loss reads both products."""
    from paddle_tpu.layer_helper import LayerHelper

    def build(d):
        helper = LayerHelper("projections")
        outs = [helper.create_variable_for_type_inference(d["x"].dtype)
                for _ in range(2)]
        groups = [(("w_gate", "w_up"), outs)] if joined else \
            [(("w_gate",), outs[:1]), (("w_up",), outs[1:])]
        for ws, results in groups:
            helper.append_op(
                "grouped_matmul",
                inputs={"X": [d["x"].name], "W": [d[w].name for w in ws],
                        "GroupSizes": [d["sizes"].name]},
                outputs={"Out": [r.name for r in results]})
        both = layers.elementwise_add(
            outs[0], layers.elementwise_mul(outs[1], d["probe_up"]))
        return both, [outs[1].name, "x@GRAD", "w_gate@GRAD", "w_up@GRAD"]

    got, main = _run_ops(build, feed)
    types = [op.type for op in main.global_block().ops]
    assert types.count("grouped_matmul") == (1 if joined else 2)
    assert types.count("grouped_matmul_grad") == (1 if joined else 2)
    # the two ops' input gradients meet in a `sum`; the one op's do not
    sums = [op for op in main.global_block().ops if op.type == "sum"
            and op.output("Out") == ["x@GRAD"]]
    assert len(sums) == (0 if joined else 1)
    return got


@pytest.mark.parametrize("cell,path", [
    *((cell, "ragged_dot") for cell in sorted(SHARE_CELLS)),
    # the interpreted kernels once, at the smallest layout
    ("mellum2", "pallas_interpreted")])
def test_two_weight_grouped_matmul_sums_its_input_gradients_on_the_used_rows(
        cell, path, monkeypatch):
    """One `grouped_matmul` with `W: [gate, up]` against the parent's two
    ops and the `sum` of their input gradients: both products, `X@GRAD` on
    the used rows and both `W@GRAD`s bit for bit, with NaN in `X` and in
    the cotangents behind the used rows."""
    _, held, width, d_model, _ = SHARE_CELLS[cell]
    rows, chunk = _layout(cell)
    if path == "pallas_interpreted":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    used = chunk + 3 * moe.ROW_TILE
    rng = np.random.RandomState(15)
    feed = {"x": rng.randn(rows, d_model).astype(np.float32),
            "w_gate": rng.randn(held, d_model, width).astype(np.float32) * .3,
            "w_up": rng.randn(held, d_model, width).astype(np.float32) * .3,
            "probe": rng.randn(rows, width).astype(np.float32),
            "probe_up": rng.randn(rows, width).astype(np.float32),
            "sizes": _sizes_using(held, used)}
    apart = _projections_piece(feed, joined=False)
    poisoned = {name: v.copy() for name, v in feed.items()}
    for name in ("x", "probe", "probe_up"):
        poisoned[name][used:] = np.nan
    joined = _projections_piece(poisoned, joined=True)
    names = ("gate + up x probe", "up", "d_x", "d_w_gate", "d_w_up")
    for name, want, got in zip(names, apart, joined):
        by_row = want.shape[0] == rows
        want, got = (want[:used], got[:used]) if by_row else (want, got)
        assert np.all(np.isfinite(got)) and np.any(got), name
        assert np.array_equal(want, got), name


def _behind_the_used_rows(value, sizes):
    used = jnp.sum(sizes.astype(jnp.int32))
    row = jax.lax.iota(jnp.int32, value.shape[0])
    shape = (-1,) + (1,) * (value.ndim - 1)
    return jnp.where((row >= used).reshape(shape), jnp.nan, value)


def test_rows_that_no_held_group_uses_are_never_read_by_the_whole_share(
        monkeypatch):
    """`moe_experts` under a share with NaN behind the used rows of every
    intermediate: each allocation (`lax.empty`: `XSorted`, the silu
    product, `dY`) filled with NaN, and every grouped
    product and input gradient given NaN behind the rows its groups use
    (where the chip's kernels leave what the buffer held). Out and all five
    gradients are finite and bit for bit the clean run's."""
    rng = np.random.RandomState(16)
    n = 96
    index = np.stack([rng.choice(16, K, replace=False) for _ in range(n)]) \
        .astype(np.int32)
    x = rng.randn(n, D).astype(np.float32)
    weight = rng.uniform(0.05, 0.4, (n, K)).astype(np.float32)
    weights = _held_weights(rng)
    (clean,), clean_grads, _ = _share_on_given_routing(index, weight, x,
                                                       weights, 8)
    filled = []

    def nan_filled(shape, dtype):
        filled.append(tuple(shape))
        return jnp.full(shape, jnp.nan, dtype)

    dot, dot_grads = moe._grouped_dot, moe._grouped_dot_grads

    def poisoned_dot(x, w, sizes, **kw):
        return _behind_the_used_rows(dot(x, w, sizes, **kw), sizes)

    def poisoned_dot_grads(x, w, g, sizes):
        d_x, d_w = dot_grads(x, w, g, sizes)
        return _behind_the_used_rows(d_x, sizes), d_w

    monkeypatch.setattr(moe.lax, "empty", nan_filled)
    monkeypatch.setattr(moe, "_grouped_dot", poisoned_dot)
    monkeypatch.setattr(moe, "_grouped_dot_grads", poisoned_dot_grads)
    (dirty,), dirty_grads, _ = _share_on_given_routing(index, weight, x,
                                                       weights, 8)
    rows = n * K + HELD * moe.ROW_TILE
    # the layout and dY by the movements, and the silu product (its two
    # gradients are written over gate and up)
    assert sorted(filled) == sorted([(rows, D)] * 2 + [(rows, F)])
    assert np.all(np.isfinite(dirty)) and np.array_equal(clean, dirty)
    assert np.any(clean)
    assert sorted(clean_grads) == ["e.down.w", "e.gate.w", "e.up.w",
                                   "weight", "x"]
    for name, g in clean_grads.items():
        assert np.all(np.isfinite(dirty_grads[name])), name
        assert np.array_equal(g, dirty_grads[name]), name
        assert np.any(g), name


@pytest.mark.parametrize("held,bounded", [(HELD, 3), (None, None)],
                         ids=["a_share", "every_expert_held"])
def test_a_share_counts_its_three_bounded_ops_a_layer(held, bounded):
    """Two expert layers in one program: under a share `swiglu`, its grad
    and the two-weight `grouped_matmul`'s grad each count themselves on the
    compile event, three a layer, beside the four movements; where every
    expert is held there is no such count."""
    rng = np.random.RandomState(17)
    x = rng.randn(64, D).astype(np.float32)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        d = layers.data(name="x", shape=[64, D], dtype="float32",
                        append_batch_size=False, stop_gradient=False)
        h = d
        for layer in range(2):
            routing = layers.moe_router(h, N_EXPERT, K)
            h = layers.moe_experts(
                h, routing, N_EXPERT, F, name=f"e{layer}",
                first_expert=None if held is None else 4, experts_held=held)
        fluid.append_backward(layers.reduce_sum(h))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    for _ in range(2):          # a second run of the step counts nothing twice
        exe.run(main, feed={"x": x}, fetch_list=["x@GRAD"], scope=scope)
    detail = observe.observatory().latest(main._uid).detail
    types = [op.type for op in main.global_block().ops]
    if held is None:
        assert "moe_share_bounded_ops" not in detail
        assert "moe_share_bounded_moves" not in detail
        assert types.count("grouped_matmul") == 3 * 2
    else:
        assert detail["moe_share_bounded_ops"] == bounded * 2
        assert detail["moe_share_bounded_moves"] == 4 * 2
        assert types.count("grouped_matmul") == 2 * 2
    with_sizes = [op for op in main.global_block().ops
                  if op.type == "swiglu" and op.inputs.get("GroupSizes")]
    assert len(with_sizes) == (0 if held is None else 2)


# -- the whole tiny model ----------------------------------------------------------

def _seeded_values(shapes, seed=3):
    """Weights far from their initial values, so that no term of the
    comparison is small by construction: zero-centred norm weights in
    [-0.5, 0.5], the gated norm's in [0.5, 1.5], a decay that forgets
    slowly (A in [0.05, 1]) so that the state carries over many chunks,
    matrices of std 0.1 (five times the initial)."""
    rng = np.random.RandomState(seed)
    values = {}
    for name in sorted(shapes):
        shape = shapes[name]
        if name.endswith("gdn.norm.w"):
            value = rng.uniform(0.5, 1.5, shape)
        elif "norm" in name:
            value = rng.uniform(-0.5, 0.5, shape)
        elif name.endswith("A_log"):
            value = np.log(rng.uniform(0.05, 1.0, shape))
        elif name.endswith("dt_bias"):
            value = rng.uniform(-1.0, 1.0, shape)
        elif name.endswith("conv.w"):
            value = rng.uniform(-0.5, 0.5, shape)
        elif name.endswith("router.w"):
            value = rng.randn(*shape) * 0.5
        else:
            value = rng.randn(*shape) * 0.1
        values[name] = value.astype(np.float32)
    return values


FETCHES = ["loss", "ce", "load_balance", "logits", "tokens_per_expert"]
CASE = DecoderCase(models.qwen3_next.build, TINY, ref, REF_KW, FETCHES,
                   seeded_values=_seeded_values)


@pytest.fixture(scope="module")
def tiny():
    return CASE.tiny_model()


GDN = ["in_norm.w", "post_norm.w", "gdn.qkvz.w", "gdn.ba.w", "gdn.conv.w",
       "gdn.A_log", "gdn.dt_bias", "gdn.norm.w", "gdn.out.w"]
ATTN = ["in_norm.w", "post_norm.w", "attn.q.w", "attn.k.w", "attn.v.w",
        "attn.o.w", "attn.q_norm.w", "attn.k_norm.w"]
MOE = ["router.w", "experts.gate.w", "experts.up.w", "experts.down.w",
       "shared.gate.w", "shared.up.w", "shared.down.w", "shared_gate.w"]
PARAM_NAMES = (["embed.w", "final_norm.w", "head.w"]
               + [f"l{i}.{n}" for i in range(4)
                  for n in (ATTN if i == 3 else GDN) + MOE])


def test_tiny_model_has_the_reference_parameters(tiny):
    CASE.has_the_reference_parameters(tiny, PARAM_NAMES, {
        "l0.experts.gate.w": (4, 32, 16), "l0.router.w": (32, 16),
        "l3.attn.q.w": (32, 4 * 2 * 16),
        "l0.gdn.qkvz.w": (32, 2 * (16 + 32))})


@pytest.mark.parametrize("name", FETCHES)
def test_tiny_model_output_matches_reference(tiny, name):
    CASE.output_matches_reference(tiny, name)


def test_tiny_routing_sends_most_assignments_elsewhere(tiny):
    CASE.routing_sends_most_assignments_elsewhere(tiny, routed_layers=4)


@pytest.mark.parametrize("name", PARAM_NAMES)
def test_tiny_model_gradient_matches_reference(tiny, name):
    CASE.gradient_matches_reference(tiny, name)


@pytest.mark.parametrize("kind,sizes", [
    ("linear_attention", dict(n_layer=1)),
    ("full_attention", dict(n_layer=1, full_attention_interval=1))])
def test_one_layer_of_each_kind_matches_reference(kind, sizes):
    main, params, feed, got, grads, _ = CASE.run_tiny(
        amp=False, seed=11, batch_seed=4, **sizes)
    assert any(("gdn" in n) == (kind == "linear_attention") for n in params
               if n.startswith("l0.") and ("gdn" in n or "attn" in n))
    want, want_grads = ref.loss_and_grads(
        params, jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"]),
        last=TINY["seq_len"], **{**REF_KW, **sizes})
    assert abs(float(got["loss"][0]) - float(want["loss"])) < 1e-5
    assert rel_err(got["logits"], want["logits"]) < 1e-4
    for name, g in grads.items():
        assert frob(g, want_grads[name]) < 2e-4, name
    detail = observe.observatory().latest(main._uid).detail
    assert detail["layer_kinds"] == {kind: 1}


def test_reference_in_blocks_is_the_reference(tiny):
    CASE.reference_in_blocks_is_the_reference(
        tiny, ["l0.gdn.qkvz.w", "l3.attn.q.w", "embed.w"], q_block=32,
        token_block=16)


def test_reference_last_positions_equal_the_full_pass(tiny):
    CASE.reference_last_positions_equal_the_full_pass(tiny)


def test_compile_event_carries_the_census():
    carries_the_census(CASE.compile_detail(), {
        "layer_kinds": {"linear_attention": 3, "full_attention": 1},
        "moe_experts_routed": 16, "moe_experts_held": 4,
        # noted by `moe_dispatch`'s rule under the trace: 2 x 128 tokens x 4
        # choices + 4 held experts x 128
        "moe_row_buffer_rows": 2 * 128 * 4 + 4 * 128,
        # dispatch, combine and their grads in each of the four layers,
        # lowered over the rows the held groups use
        # (`ops/moe.py::_over_used_rows`)
        "moe_share_bounded_moves": 4 * 4, "grad_fanin_max": 1},
        # the startup program has neither mixers nor experts
        startup_lacks=["layer_kinds"])


def test_the_rows_on_the_compile_event_follow_the_batch():
    """`moe_row_buffer_rows` is what the rule laid out, not a setting: one
    sequence instead of two is another compile of the same program with
    fewer rows; a rule called outside a lowering notes nothing."""
    main, startup, fetches, _ = CASE.program(
        fluid.optimizer.SGD(learning_rate=1e-3), n_layer=1)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    one = {k: v[:1] for k, v in CASE.batch().items()}
    exe.run(main, feed=one, fetch_list=[fetches["loss"]], scope=scope)
    assert observe.observatory().latest(main._uid).detail[
        "moe_row_buffer_rows"] == 128 * 4 + 4 * 128
    from paddle_tpu.core.registry import LoweringContext
    LoweringContext({}).note(moe_row_buffer_rows=1)     # no lowerer: nothing


def test_every_layer_is_built_under_its_name_scopes(tiny):
    layers_are_built_under_their_scopes(
        tiny["main"],
        ["l0.gdn", "l1.gdn", "l2.gdn", "l3.attn", "l0.moe", "l1.moe",
         "l2.moe", "l3.moe"], absent=["l3.gdn", "l0.attn"],
        holds={"l0.gdn": ["gated_delta_rule", "delta_rule_gates",
                          "causal_conv1d", "gated_rms_norm"],
               "l3.attn": ["fused_attention"],
               "l2.moe": ["moe_router", "moe_dispatch", "grouped_matmul",
                          "moe_combine"]})


def test_tiny_model_amp_within_bf16_of_reference():
    """Under AMP the residual stream, the projections and the experts are
    bf16; the router, g, beta, the rule's sums and state and every norm's
    statistics stay float32. Against the float32 reference that is bf16
    rounding (2^-8 relative) compounded over four layers. At the initial
    weights: with the seeded ones (a router five times as sharp) a few of
    the 1024 assignments flip under bf16 inputs and move the gradients by
    more than the rounding does."""
    CASE.amp_within_bf16_of_reference(
        {0.04: ("l0.gdn.qkvz.w", "l0.gdn.conv.w", "l3.attn.q.w",
                "l0.experts.gate.w", "l0.shared.gate.w", "embed.w")})


def test_amp_keeps_the_gates_and_the_router_in_float32():
    main, startup, fetches, _ = CASE.program(n_layer=1)
    block = main.global_block()
    gates = next(o for o in block.ops if o.type == "delta_rule_gates")
    from paddle_tpu.core import registry
    assert "delta_rule_gates" in registry.AMP_F32_OPS
    assert "moe_router" in registry.AMP_F32_OPS
    assert "gated_delta_rule" not in registry.AMP_F32_OPS | \
        registry.AMP_BF16_OPS
    assert block.var(gates.output("G")[0]).dtype == "float32"


def test_five_adam_steps_lower_the_loss():
    CASE.adam_steps_lower_the_loss()


def test_a_log_starts_as_the_log_of_a_uniform_draw_and_dt_bias_at_one():
    main, startup, _, _ = CASE.program(n_layer=1)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    a = np.exp(np.asarray(scope.find_var("l0.gdn.A_log")))
    assert a.shape == (4,) and np.all(a > 0) and np.all(a < 16)
    assert np.all(np.asarray(scope.find_var("l0.gdn.dt_bias")) == 1)
    conv = np.asarray(scope.find_var("l0.gdn.conv.w"))
    assert conv.shape == (2 * 2 * 8 + 4 * 8, 4) and np.abs(conv).max() <= 0.5


# -- OLMoE is what it was ------------------------------------------------------------

def test_olmoe_program_is_unchanged_op_for_op():
    """The expert layer, the router, rms_norm and rotary_embedding took new
    attributes in this file's PR; a program that passes none of them is the
    program it was (`decoder_case.DIGESTS`)."""
    main, startup, feeds, fetches = build_program("olmoe")
    assert program_digest(main, startup) == DIGESTS["olmoe"]
    # and its movements are the static ones: every row is an assignment
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    exe.run(main, feed={n: rng.randint(0, 128, (1, 128)).astype(np.int64)
                        for n in feeds}, fetch_list=[fetches["loss"]],
            scope=scope)
    detail = observe.observatory().latest(main._uid).detail
    assert "moe_share_bounded_moves" not in detail
    assert "moe_row_buffer_rows" not in detail


def test_the_two_copies_of_the_reference_are_identical():
    CASE.two_copies_of_the_reference_are_identical()
