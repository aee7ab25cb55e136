"""OLMoE through `layers` -> Program IR -> `Executor`, against the plain
reference (`tests/olmoe_reference.py`): each new op against its piece of the
reference, forward and gradient, then the whole tiny model. Seeded random
weights, float32, AMP off unless a test says otherwise."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models

import olmoe_reference as ref
from decoder_case import DecoderCase, rel_err, run_piece, tiny_args

TINY = tiny_args("olmoe")
REF_KW = dict(n_layer=2, n_head=2, top_k=2)
# float32 against float32 highest: the two sides differ by the order of
# their sums (the grouped matmul sums a group, the reference a dense mask;
# the flash reference path and the einsum), a few ulp of 6e-8 each
RTOL = 1e-5


# -- ops against their piece of the reference ---------------------------------

def test_rms_norm_matches_reference():
    rng = np.random.RandomState(0)
    x = rng.randn(3, 5, 16).astype(np.float32)
    w = rng.uniform(0.5, 1.5, 16).astype(np.float32)
    (y,), grads, probe = run_piece(
        lambda d: [layers.rms_norm(d["x"], epsilon=1e-5,
                                   param_attr=fluid.ParamAttr(name="w"))],
        {"x": x}, {"w": w})
    want = ref.rms_norm(x, w, 1e-5)
    gx, gw = jax.grad(lambda a, b: jnp.sum(ref.rms_norm(a, b, 1e-5) * probe),
                      (0, 1))(x, w)
    assert rel_err(y, want) < RTOL
    assert rel_err(grads["x"], gx) < RTOL
    assert rel_err(grads["w"], gw) < RTOL


def test_rotary_embedding_matches_reference():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 3, 24, 8).astype(np.float32)
    (y,), grads, probe = run_piece(
        lambda d: [layers.rotary_embedding(d["x"], theta=10000.0)], {"x": x})
    assert rel_err(y, ref.rotary(x, 10000.0)) < RTOL
    gx = jax.grad(lambda a: jnp.sum(ref.rotary(a, 10000.0) * probe))(x)
    assert rel_err(grads["x"], gx) < RTOL
    # position 0 is left as it is; a rotation keeps each pair's length
    np.testing.assert_allclose(y[:, :, 0], x[:, :, 0], rtol=1e-6)
    np.testing.assert_allclose(
        y[..., :4] ** 2 + y[..., 4:] ** 2, x[..., :4] ** 2 + x[..., 4:] ** 2,
        rtol=1e-4, atol=1e-6)


def test_swiglu_matches_silu_times_up():
    rng = np.random.RandomState(2)
    g, u = (rng.randn(6, 10).astype(np.float32) for _ in range(2))
    (y,), grads, probe = run_piece(
        lambda d: [layers.swiglu(d["g"], d["u"])], {"g": g, "u": u})
    assert rel_err(y, jax.nn.silu(g) * u) < RTOL
    gg, gu = jax.grad(lambda a, b: jnp.sum(jax.nn.silu(a) * b * probe),
                      (0, 1))(g, u)
    assert rel_err(grads["g"], gg) < RTOL
    assert rel_err(grads["u"], gu) < RTOL


def test_router_matches_reference():
    rng = np.random.RandomState(3)
    x = rng.randn(40, 16).astype(np.float32)
    w = rng.randn(16, 8).astype(np.float32)

    def build(d):
        r = layers.moe_router(d["x"], 8, 3,
                              param_attr=fluid.ParamAttr(name="r.w"))
        return [r["weight"], r["index"], r["tokens_per_expert"], r["probs"],
                r["logsumexp"]]

    (weight, index, counts, probs, lse), grads, probe = run_piece(
        build, {"x": x}, {"r.w": w})
    p = {"l.router.w": w}
    logits, want_probs, want_weight, want_index = ref.router(p, "l", x, 3)
    np.testing.assert_array_equal(index, want_index)
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(want_index).reshape(-1), minlength=8))
    assert counts.sum() == 40 * 3
    assert rel_err(weight, want_weight) < RTOL
    assert rel_err(probs, want_probs) < RTOL
    assert rel_err(lse, jax.nn.logsumexp(logits, axis=-1)) < RTOL
    # softmax over all 8, and the 3 weights are not renormalised
    np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-5)
    assert np.all(weight.sum(-1) < 1.0)
    gx, gw = jax.grad(
        lambda a, b: jnp.sum(ref.router({"l.router.w": b}, "l", a, 3)[2]
                             * probe), (0, 1))(x, w)
    assert rel_err(grads["x"], gx) < RTOL
    assert rel_err(grads["r.w"], gw) < RTOL


def _routing(kind, n, n_expert, k, rng):
    """Distinct experts per token. `skewed`: experts 0 and 1 get nothing,
    expert 2 gets a slot of every token."""
    if kind == "even":
        return np.stack([rng.permutation(n_expert)[:k] for _ in range(n)])
    rest = np.arange(3, n_expert)
    return np.stack([np.concatenate([[2], rng.permutation(rest)[:k - 1]])
                     for _ in range(n)])


@pytest.mark.parametrize("kind", ["even", "skewed"])
def test_dispatch_lays_groups_out_in_whole_tiles(kind):
    """Every group starts and ends on a row tile and has at least one, the
    groups fill the rows, each assignment sits in its expert's group, a
    padding row is zero; so the grouped kernel visits every tile once,
    whatever the routing: its tile table is as long under `skewed` (two
    experts empty, one with every token) as under `even`."""
    import importlib
    from paddle_tpu.ops.moe import _moe_dispatch
    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    rng = np.random.RandomState(5)
    n, d, n_expert, k, tile = 48, 4, 8, 3, 16
    x = rng.randn(n, d).astype(np.float32)
    index = _routing(kind, n, n_expert, k, rng).astype(np.int32)
    counts = np.bincount(index.reshape(-1), minlength=n_expert) \
        .astype(np.int32)

    class Ctx:
        attr = staticmethod({"row_tile": tile}.get)
    out = {name: np.asarray(v) for name, v in _moe_dispatch(
        Ctx, jnp.asarray(x), jnp.asarray(index), jnp.asarray(counts)).items()}
    rows = n * k + n_expert * tile
    sizes, slot, source = out["GroupSizes"], out["Slot"], out["Source"]
    assert out["XSorted"].shape == (rows, d) and sizes.sum() == rows
    assert np.all(sizes % tile == 0) and np.all(sizes >= tile)
    assert np.all(sizes[:-1] - counts[:-1] < 2 * tile)
    group_of_row = np.repeat(np.arange(n_expert), sizes)
    np.testing.assert_array_equal(group_of_row[slot], index.reshape(-1))
    np.testing.assert_array_equal(source[slot], np.arange(n * k))
    assert (source >= 0).sum() == n * k
    np.testing.assert_array_equal(out["XSorted"][slot],
                                  np.repeat(x, k, axis=0))
    assert not np.any(out["XSorted"][source < 0])
    # a stable sort: a group's assignments keep their order
    for e in range(n_expert):
        held = source[group_of_row == e]
        assert np.all(np.diff(held[held >= 0]) > 0)
    for visit_empty in (False, True):       # gmm, tgmm
        _, visits = gmm.make_group_metadata(
            group_sizes=jnp.asarray(sizes), m=rows, tm=tile,
            start_group=jnp.int32(0), num_nonzero_groups=n_expert,
            visit_empty_groups=visit_empty)
        assert int(visits) == rows // tile


@pytest.mark.parametrize("kind", ["even", "skewed"])
@pytest.mark.parametrize("path", ["ragged_dot", "pallas_interpreted"])
def test_expert_layer_matches_reference(kind, path, monkeypatch):
    """Forward, input gradient and weight gradient of the expert layer, with
    two experts left empty under `skewed`. `pallas_interpreted` is the chip's
    path, the megablox gmm / tgmm kernels with the op's own hand-written
    gradient, under the Pallas interpreter; `ragged_dot` is what a CPU
    backend runs otherwise."""
    from paddle_tpu.ops import moe
    if path == "pallas_interpreted":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert (moe._kernel() is not None) == (path == "pallas_interpreted")
    rng = np.random.RandomState(4)
    n, d, f, n_expert, k = 48, 16, 12, 8, 3
    x = rng.randn(n, d).astype(np.float32)
    index = _routing(kind, n, n_expert, k, rng).astype(np.int32)
    weight = rng.uniform(0.05, 0.4, (n, k)).astype(np.float32)
    counts = np.bincount(index.reshape(-1), minlength=n_expert) \
        .astype(np.int32)
    if kind == "skewed":
        assert counts[0] == counts[1] == 0 and counts[2] == n
    weights = {"e.gate.w": rng.randn(n_expert, d, f).astype(np.float32) * .3,
               "e.up.w": rng.randn(n_expert, d, f).astype(np.float32) * .3,
               "e.down.w": rng.randn(n_expert, f, d).astype(np.float32) * .3}

    def build(data):
        routing = {"weight": data["weight"], "index": data["index"],
                   "tokens_per_expert": data["counts"]}
        return [layers.moe_experts(data["x"], routing, n_expert, f, name="e")]

    (y,), grads, probe = run_piece(
        build, {"x": x, "weight": weight, "index": index, "counts": counts},
        weights)

    def want(x, weight, params):
        p = {"l.experts." + k.split(".", 1)[1]: v for k, v in params.items()}
        return ref.experts(p, "l", x, weight, index)

    with jax.default_matmul_precision("highest"):
        assert rel_err(y, want(x, weight, weights)) < RTOL
        gx, gweight, gparams = jax.grad(
            lambda a, b, c: jnp.sum(want(a, b, c) * probe),
            (0, 1, 2))(x, weight, weights)
    assert rel_err(grads["x"], gx) < RTOL
    assert rel_err(grads["weight"], gweight) < RTOL
    for name in weights:
        assert rel_err(grads[name], gparams[name]) < RTOL, name
        if kind == "skewed":     # an expert no token chose learns nothing
            assert not np.any(grads[name][:2])


# -- the whole tiny model --------------------------------------------------------

FETCHES = ["loss", "ce", "load_balance", "z_loss", "logits",
           "tokens_per_expert"]
# the largest entry's error for a gradient too: no router is sharp here
CASE = DecoderCase(models.olmoe.build, TINY, ref, REF_KW, FETCHES,
                   interpreted=True, out_tol=RTOL, grad_tol=RTOL,
                   grad_err=rel_err)


@pytest.fixture(scope="module")
def tiny():
    """The tiny model at its initial weights through the chip's kernels
    (megablox gmm / tgmm with the op's own gradient, the flash kernels),
    interpreted on the CPU."""
    return CASE.tiny_model(last=None)


PARAM_NAMES = (["embed.w", "final_norm.w", "head.w"]
               + [f"l{i}.{n}.w" for i in range(TINY["n_layer"])
                  for n in ("attn_norm", "q", "k", "v", "q_norm", "k_norm",
                            "o", "moe_norm", "router", "experts.gate",
                            "experts.up", "experts.down")])


def test_tiny_model_has_the_reference_parameters(tiny):
    CASE.has_the_reference_parameters(tiny, PARAM_NAMES)


@pytest.mark.parametrize("name", FETCHES)
def test_tiny_model_output_matches_reference(tiny, name):
    CASE.output_matches_reference(tiny, name)
    if name == "tokens_per_expert":
        assert tiny["got"][name].shape == (2, 8)
        assert np.all(tiny["got"][name].sum(-1) == 2 * 128 * 2)


@pytest.mark.parametrize("name", PARAM_NAMES)
def test_tiny_model_gradient_matches_reference(tiny, name):
    CASE.gradient_matches_reference(tiny, name)


def test_reference_with_given_routing_equals_its_own(tiny):
    want = tiny["want"]
    again = ref.loss_parts(tiny["params"], tiny["feed"]["tokens"],
                           tiny["feed"]["labels"], routing=want["index"],
                           **REF_KW)
    for name in ("loss", "ce", "load_balance", "z_loss"):
        assert float(again[name]) == float(want[name]), name


# its own body: this reference's `last` is `forward`'s, not `loss_parts`'
def test_reference_last_positions_equal_the_full_pass(tiny):
    """The chip check compares the last positions against the whole
    context; that path must be the full forward pass's tail."""
    logits, _ = ref.forward(tiny["params"], tiny["feed"]["tokens"], last=16,
                            **REF_KW)
    assert rel_err(logits, np.asarray(tiny["want"]["logits"])[:, -16:]) < RTOL


# its own body: against `tiny`'s reference, every loss part and the counts
def test_tiny_model_amp_within_bf16_of_reference(tiny):
    """AMP on: projections and expert matmuls in bf16 (8 bits of mantissa,
    relative rounding 2^-9 = 0.002 an operand), router, norms' statistics
    and losses in float32. Logits are O(1) sums of 64 such products, so they
    move by about 0.01; a loss is a mean over 256 positions and moves far
    less. bf16 may flip a near-tie in the router, which moves counts by a
    few assignments and, with them, whole rows of two experts' gradients:
    gradients are therefore compared in the Frobenius norm, not entry by
    entry (read: 0.5-1% outside the experts, 6-9% in them with 6 of 1024
    assignments flipped; losses within 1e-5 to 2e-4)."""
    _, _, _, got, grads, _ = CASE.run_tiny(amp=True)
    want = tiny["want"]
    assert np.max(np.abs(np.asarray(got["logits"], np.float32)
                         - np.asarray(want["logits"]))) < 0.03
    for name, atol in (("loss", 1e-3), ("ce", 1e-3), ("load_balance", 5e-3),
                       ("z_loss", 5e-3)):
        assert abs(float(np.asarray(got[name]).reshape(-1)[0])
                   - float(want[name])) < atol, name
    moved = np.abs(np.asarray(got["tokens_per_expert"], np.int64)
                   - np.asarray(want["tokens_per_expert"], np.int64))
    assert moved.sum() <= 0.02 * 2 * 2 * 128 * 2
    for name in ("head.w", "l0.experts.gate.w", "l1.router.w", "embed.w"):
        assert grads[name].dtype == np.float32
        want_grad = np.asarray(tiny["want_grads"][name], np.float64)
        assert (np.linalg.norm(grads[name] - want_grad)
                < 0.2 * np.linalg.norm(want_grad)), name


def test_five_adam_steps_lower_the_loss():
    CASE.adam_steps_lower_the_loss(lr=4e-3, seed=5, steps=5)


def test_the_two_copies_of_the_reference_are_identical():
    CASE.two_copies_of_the_reference_are_identical()
