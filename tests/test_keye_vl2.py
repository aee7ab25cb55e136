"""Keye-VL-2.0's language model (a learned selection of `topk` keys a query,
DeepSeek-Sparse-Attention's indexer over grouped heads, through
`dsa_index_scores`, `dsa_select` and `fused_attention(kept=...)`; one chip's
share of a renormalised top-k expert layer in every layer) through `layers`
-> Program IR -> `Executor`, against the plain reference
(`tests/keye_vl2_reference.py`: an einsum, `jax.lax.top_k`, a masked softmax,
`jnp.repeat`, a loop over the held experts). Two comparisons, as on the chip:
the kept sets, and with the reference handed the system's kept sets the
logits, the loss and the gradients. Seeded random weights, float32, AMP off
unless a test says otherwise."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, models, observe
from paddle_tpu.core import registry
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops import sparse_attention as sa

import keye_vl2_reference as ref
from attention_program import flash_calls
from decoder_case import (DecoderCase, carries_the_census, config, frob,
                          layers_are_built_under_their_scopes, rel_err,
                          run_piece, runs_through_the_benchmark, tiny_args)

CONFIG = config("keye_vl2")
# the configuration's tiny block at a narrower model: 256 tokens, topk 64,
# index heads 4 x 16, 16 experts of which 4 held from expert 4
TINY = tiny_args("keye_vl2")
REF_KW = {k: TINY[k] for k in (
    "n_layer", "n_head", "n_kv_head", "head_dim", "rope_theta",
    "n_index_head", "index_dim", "topk", "top_k", "first_expert")}
T, K = TINY["seq_len"], TINY["topk"]
ROW_KEEPS = np.minimum(np.arange(T) + 1, K)
RTOL = 2e-5


# -- the selection ----------------------------------------------------------------

def _brute_force(scores, topk):
    """Row t keeps its min(t + 1, topk) largest of s <= t, ties to the lower
    index: a stable sort a row."""
    scores = np.asarray(scores)
    out = np.zeros(scores.shape, np.int8)
    for b in range(scores.shape[0]):
        for t in range(scores.shape[1]):
            row = scores[b, t, :t + 1]
            order = np.lexsort((np.arange(t + 1), -row))
            out[b, t, order[:min(t + 1, topk)]] = 1
    return out


def _index_operands(seed=0, batch=2, heads=4, dim=16, seq=T):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(batch, heads, seq, dim), jnp.float32),
            jnp.asarray(rng.randn(batch, 1, seq, dim), jnp.float32),
            jnp.asarray(rng.randn(batch, seq, heads), jnp.float32))


@pytest.fixture(scope="module")
def tied_scores():
    """Index scores rounded to halves: most rows have equal scores at their
    threshold."""
    q, k, w = _index_operands()
    s = np.asarray(sa.index_scores_xla(q, k, w, 0.125, 64))
    return np.where(np.isfinite(s), np.round(s * 2) / 2, s)


def test_index_scores_are_the_formula():
    q, k, w = _index_operands()
    got = np.asarray(sa.index_scores_xla(q, k, w, 0.125, 64))
    want = np.einsum("bhqd,bkd->bhqk", np.asarray(q), np.asarray(k[:, 0]))
    want = (np.maximum(want, 0) * np.asarray(w).transpose(0, 2, 1)[..., None]
            ).sum(1) * 0.125
    below = np.tril(np.ones((T, T), bool))
    assert np.all(np.isneginf(got[:, ~below]))
    got = np.where(below, got, 0)
    assert np.abs(got - np.where(below, want, 0)).max() < 1e-5
    theirs = ref.index_scores(q, k[:, 0], w) * (0.125 / (16 ** -0.5 * 0.5))
    assert np.abs(got - np.where(below, np.asarray(theirs), 0)).max() < 1e-5


@pytest.mark.parametrize("tile", [64, 128])
def test_scores_computed_in_tiles_equal_scores_computed_whole(tile):
    q, k, w = _index_operands(seed=1)
    assert np.array_equal(sa.index_scores_xla(q, k, w, 0.125, tile),
                          sa.index_scores_xla(q, k, w, 0.125, T))


def test_interpreted_score_kernel_is_the_jnp_form(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    q, k, w = _index_operands(seed=2)
    got = np.asarray(sa.index_scores_kernel(q, k, w, 0.125, 128))
    want = np.asarray(sa.index_scores_xla(q, k, w, 0.125, 128))
    assert np.array_equal(np.isneginf(got), np.isneginf(want))
    live = np.isfinite(want)
    assert np.abs(np.where(live, got, 0) - np.where(live, want, 0)).max() \
        < 1e-5


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("topk", [1, 64, 130, 300])
def test_every_row_keeps_its_largest_keys_ties_to_the_lower_index(
        tied_scores, monkeypatch, form, topk):
    """Exactly min(t + 1, topk) a row, never a future key, of equal scores
    the lower index: the sort-based form and the interpreted bisection
    kernel (a strip of rows under `topk`, a strip across it, strips above)
    against a stable sort a row."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    select = sa.select_xla if form == "xla" else sa.select_kernel
    got = np.asarray(select(jnp.asarray(tied_scores), topk))
    assert got.dtype == np.int8
    assert np.array_equal(got.sum(-1)[0], np.minimum(np.arange(T) + 1, topk))
    assert not got[:, ~np.tril(np.ones((T, T), bool))].any()
    assert np.array_equal(got, _brute_force(tied_scores, topk))


def test_negative_zero_ties_with_zero():
    s = np.full((1, 128, 128), -np.inf, np.float32)
    s[0, 100, :101] = np.where(np.arange(101) % 2, -0.0, 0.0)
    s[0, :100, 0] = 1.0
    s[0, 101:, 0] = 1.0
    got = np.asarray(sa.select_xla(jnp.asarray(s), 10))
    assert np.array_equal(np.nonzero(got[0, 100])[0], np.arange(10))


def test_topk_over_the_sequence_is_the_causal_triangle():
    q, k, w = _index_operands(seed=3)
    s = sa.index_scores_xla(q, k, w, 0.125, 64)
    assert np.array_equal(np.asarray(sa.select_xla(s, T + 5))[0],
                          np.tril(np.ones((T, T), np.int8)))


# -- the kernels under a kept set -----------------------------------------------

def _attention_operands(seq, seed=0, batch=2, heads=2, dim=32):
    rng = np.random.RandomState(seed)
    q, k, v, g = (jnp.asarray(rng.randn(batch, heads, seq, dim) * 0.5,
                              jnp.float32) for _ in range(4))
    q_i, k_i, w_i = _index_operands(seed + 1, batch, 2, 16, seq)
    kept = sa.select_xla(sa.index_scores_xla(q_i, k_i, w_i, 0.2, 128),
                         seq // 4)
    return q, k, v, g, kept


def _masked_softmax(q, k, v, kept, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    p = jax.nn.softmax(jnp.where(kept[:, None] != 0, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


PLANS = {"onepass_fused": (256, None, "fused"),
         "stream_fused": (512, (128, 128), "fused"),
         "stream_split": (512, (128, 256), "split")}


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_interpreted_dsa_kernels_are_the_masked_softmax(monkeypatch, plan):
    """`dsa_flash_fwd` and the backward (the fused kernel, and the split pair
    under the plan forced to it) under a selection of a quarter of the keys,
    where whole rows of a tile hold no kept key: forward and all three
    gradients against the softmax under the mask."""
    seq, tiles, bwd = PLANS[plan]
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(pa, "_BLOCK_OVERRIDE", tiles)
    if bwd == "split":
        monkeypatch.setattr(pa, "_bwd_plan", lambda *a: "split")
    q, k, v, g, kept = _attention_operands(seq)
    scale = q.shape[-1] ** -0.5
    out, vjp = jax.vjp(lambda q, k, v: pa.flash_attention(
        q, k, v, 0, causal=True, sm_scale=scale, kept=kept), q, k, v)
    want, want_vjp = jax.vjp(
        lambda q, k, v: _masked_softmax(q, k, v, kept, scale), q, k, v)
    assert rel_err(out, want) < RTOL
    for got, ref_grad in zip(vjp(g), want_vjp(g)):
        assert rel_err(got, ref_grad) < 5e-5


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_a_kept_set_of_all_ones_is_bitwise_the_causal_call(monkeypatch, plan):
    seq, tiles, bwd = PLANS[plan]
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(pa, "_BLOCK_OVERRIDE", tiles)
    if bwd == "split":
        monkeypatch.setattr(pa, "_bwd_plan", lambda *a: "split")
    q, k, v, g, _ = _attention_operands(seq, seed=4)
    ones = jnp.ones((q.shape[0], seq, seq), jnp.int8)
    scale = q.shape[-1] ** -0.5
    out, lse = pa._flash_forward(q, k, v, True, scale, kept=ones)
    plain, plain_lse = pa._flash_forward(q, k, v, True, scale)
    assert np.array_equal(out, plain) and np.array_equal(lse, plain_lse)
    grads = pa._flash_backward(q, k, v, out, lse, g, True, scale, 0.0, 0,
                               kept=ones)
    for got, want in zip(grads, pa._flash_backward(
            q, k, v, plain, plain_lse, g, True, scale, 0.0, 0)):
        assert np.array_equal(got, want)


@pytest.mark.parametrize("q_inner", [False, True], ids=["k_inner", "q_inner"])
@pytest.mark.parametrize("seq,bq,bk", [(8192, 1024, 1024), (512, 128, 256),
                                       (512, 256, 128)])
def test_a_dead_step_fetches_no_tile_of_the_kept_set(monkeypatch, seq, bq, bk,
                                                     q_inner):
    """The kept set's index map over a causal grid in the order its steps
    run, k blocks innermost (the forward, dQ) or q blocks (the fused
    backward, dK/dV), through the maps every operand of the call shares
    (`_forward`, `_bwd_specs`; since PR 70 the set's has no clamp of its
    own): a live step reads its own tile, a step wholly above the diagonal
    the tile of the live step beside it, so the block index changes (a tile
    is fetched) once a live tile and never for a dead step: 32 x 36 times a
    call at the cell's 8192 tokens where the grid has 32 x 64 steps."""
    calls = flash_calls(monkeypatch, seq, (bq, bk), kept=True, split=True)
    assert sorted(calls) == ["dsa_flash_dkv", "dsa_flash_dq", "dsa_flash_fwd"]
    heads, nq = 2, seq // bq        # `flash_calls`: two batch rows of two
    for name in ("dsa_flash_dkv",) if q_inner else ("dsa_flash_fwd",
                                                    "dsa_flash_dq"):
        grid, specs = calls[name]
        q_ax, k_ax = (2, 1) if q_inner else (1, 2)
        fetched, at, live = 0, None, 0
        for g in np.ndindex(*grid):
            block = tuple(int(i) for i in specs[-1].index_map(*g))
            qi, kj = g[q_ax], g[k_ax]
            if pa._causal_live(qi, kj, bq, bk):
                live += 1
                assert block == (g[0] // heads, qi, kj)
            fetched, at = fetched + (block != at), block
        assert live == grid[0] * sum(pa._last_k(qi, bq, bk) + 1
                                     for qi in range(nq))
        assert fetched == live < int(np.prod(grid))
        if seq == 8192:
            assert (live, int(np.prod(grid))) == (grid[0] * 36, grid[0] * 64)


@pytest.mark.parametrize("why,kw", [
    ("not causal", dict(causal=False)),
    ("a window", dict(causal=True, window=64)),
    ("token-major", dict(causal=True, token_major=True)),
    ("float32 set", dict(causal=True, kept="float32")),
    ("wrong shape", dict(causal=True, kept="short"))])
def test_a_kept_set_is_refused_where_the_kernels_cannot_take_it(why, kw):
    q, k, v, _, kept = _attention_operands(256)
    if kw.get("kept") == "float32":
        kw["kept"] = kept.astype(jnp.float32)
    elif kw.get("kept") == "short":
        kw["kept"] = kept[:, :128]
    else:
        kw["kept"] = kept
    with pytest.raises(ValueError, match="kept set"):
        pa.flash_attention(q, k, v, 0, sm_scale=1.0, **kw)


def test_the_op_under_a_kept_set_is_the_masked_softmax():
    """`layers.fused_attention(kept=...)` on the CPU's jnp path, forward and
    the grad op; the kept set gets no gradient."""
    q, k, v, _, kept = _attention_operands(256, seed=5)
    scale = q.shape[-1] ** -0.5
    feed = {"q": np.asarray(q), "k": np.asarray(k), "v": np.asarray(v),
            "kept": np.asarray(kept)}
    (out,), grads, probe = run_piece(
        lambda d: [layers.fused_attention(d["q"], d["k"], d["v"], causal=True,
                                          kept=d["kept"], topk=64)], feed)
    want, vjp = jax.vjp(
        lambda q, k, v: _masked_softmax(q, k, v, kept, scale), q, k, v)
    assert rel_err(out, want) < RTOL
    for name, g in zip("qkv", vjp(jnp.asarray(probe))):
        assert rel_err(grads[name], g) < 5e-5
    assert set(grads) == {"q", "k", "v"}
    with pytest.raises(ValueError, match="kept set"):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = layers.data(name="x", shape=[1, 2, 128, 16], dtype="float32",
                            append_batch_size=False)
            m = layers.data(name="m", shape=[1, 128, 128], dtype="int8",
                            append_batch_size=False)
            layers.fused_attention(x, x, x, causal=True, window=32, kept=m)


# -- the shares add up -----------------------------------------------------------------

def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """The routed parts that 16 shares of one expert each give are the uncut
    reference's whole layer (softmax over all 16, top-4 renormalised over all
    four chosen, whichever share holds them)."""
    n_expert, k, d, f = TINY["n_expert"], TINY["top_k"], 16, 12
    rng = np.random.RandomState(5)
    x = rng.randn(40, d).astype(np.float32)
    whole = {"router.w": rng.randn(d, n_expert),
             "experts.gate.w": rng.randn(n_expert, d, f) * 0.3,
             "experts.up.w": rng.randn(n_expert, d, f) * 0.3,
             "experts.down.w": rng.randn(n_expert, f, d) * 0.3}
    whole = {n: v.astype(np.float32) for n, v in whole.items()}
    cut = {f"s{j}.{which}.w": whole[f"experts.{which}.w"][j:j + 1]
           for j in range(n_expert) for which in ("gate", "up", "down")}

    def build(data):
        routing = layers.moe_router(
            data["x"], n_expert, k, norm_topk_prob=True,
            param_attr=fluid.ParamAttr(name="router.w"))
        parts = [layers.moe_experts(data["x"], routing, n_expert, f,
                                    name=f"s{j}", first_expert=j,
                                    experts_held=1)
                 for j in range(n_expert)]
        return [layers.sums(parts)] + parts

    outs, _, _ = run_piece(build, {"x": x},
                           {"router.w": whole["router.w"], **cut})
    with jax.default_matmul_precision("highest"):
        want = ref.sparse_experts(whole, x, top_k=k, first_expert=0)[0]
        assert rel_err(outs[0], want) < RTOL
        for j in (0, 7, 15):
            held = {n: (v[j:j + 1] if n.startswith("experts.") else v)
                    for n, v in whole.items()}
            alone = ref.sparse_experts(held, x, top_k=k, first_expert=j)[0]
            assert rel_err(outs[1 + j], alone) < 1e-4, j


# -- the model ----------------------------------------------------------------------------

def _seeded_values(shapes, seed=3):
    """Weights far from their initial values: norm weights in [0.5, 1.5],
    the LayerNorm's bias and the matrices of std 0.1, a sharper router."""
    rng = np.random.RandomState(seed)
    values = {}
    for name in sorted(shapes):
        shape = shapes[name]
        if name.endswith("norm.w"):
            value = rng.uniform(0.5, 1.5, shape)
        elif name.endswith("router.w"):
            value = rng.randn(*shape) * 0.5
        else:
            value = rng.randn(*shape) * 0.1
        values[name] = value.astype(np.float32)
    return values


FETCHES = ["loss", "ce", "load_balance", "logits", "tokens_per_expert"]
KEPT = [f"l{i}.kept" for i in range(TINY["n_layer"])]
LAYER = ["in_norm.w", "post_norm.w", "attn.q.w", "attn.k.w", "attn.v.w",
         "attn.q_norm.w", "attn.k_norm.w", "attn.o.w", "router.w",
         "experts.gate.w", "experts.up.w", "experts.down.w"]
INDEXER = ["index.q.w", "index.k.w", "index.k_norm.w", "index.k_norm.b",
           "index.w.w"]
TRAINED = (["embed.w", "final_norm.w", "head.w"]
           + [f"l{i}.{n}" for i in range(TINY["n_layer"]) for n in LAYER])
FROZEN = [f"l{i}.{n}" for i in range(TINY["n_layer"]) for n in INDEXER]
CASE = DecoderCase(models.keye_vl2.build, TINY, ref, REF_KW, FETCHES + KEPT,
                   state=FROZEN, seeded_values=_seeded_values)


def _agreement(got, want):
    """The share of the kept pairs of either side that both sides keep."""
    got, want = np.asarray(got) != 0, np.asarray(want) != 0
    return float((got & want).sum() / (got | want).sum())


@pytest.fixture(scope="module")
def tiny():
    """The reference twice, as on the chip: its own selection (`own`), and
    handed the system's kept sets (`want`)."""
    run = CASE.run_tiny(amp=False)
    made = CASE.tiny_model(run, kept=[run.got[n] for n in KEPT])
    made["own"] = ref.loss_parts(made["params"], made["tokens"],
                                 made["labels"], return_kept=True, **REF_KW)
    return made


def test_tiny_model_has_the_reference_parameters(tiny):
    CASE.has_the_reference_parameters(tiny, TRAINED, {
        "l0.attn.q.w": (32, 4 * 16), "l0.attn.k.w": (32, 2 * 16),
        "l1.index.q.w": (32, 4 * 16), "l1.index.k.w": (32, 16),
        "l1.index.w.w": (32, 4), "l2.index.k_norm.b": (16,),
        "l1.experts.gate.w": (4, 32, 16)})


@pytest.mark.parametrize("name", KEPT)
def test_kept_sets_are_the_references(tiny, name):
    """Comparison (a): every row keeps exactly min(t + 1, topk), no future
    key, and the reference's own selection (float32, `jax.lax.top_k`) keeps
    the same pairs."""
    got = tiny["got"][name]
    assert got.dtype == np.int8 and got.shape == (2, T, T)
    assert np.array_equal(got.sum(-1), np.broadcast_to(ROW_KEEPS, (2, T)))
    assert not got[:, ~np.tril(np.ones((T, T), bool))].any()
    want = tiny["own"]["kept"][KEPT.index(name)]
    assert _agreement(got, want) >= 0.9995


@pytest.mark.parametrize("name", FETCHES)
def test_tiny_model_output_matches_reference(tiny, name):
    """Comparison (b): the reference under the system's kept sets."""
    CASE.output_matches_reference(tiny, name)


@pytest.mark.parametrize("name", TRAINED)
def test_tiny_model_gradient_matches_reference(tiny, name):
    CASE.gradient_matches_reference(tiny, name)


@pytest.mark.parametrize("name", FROZEN)
def test_the_reference_sends_no_gradient_to_the_indexer(tiny, name):
    assert not np.any(np.asarray(tiny["want_grads"][name]))


def test_the_reference_alone_agrees_with_itself_under_its_own_sets(tiny):
    """Handing the reference its own kept sets is the reference."""
    again = ref.loss_parts(tiny["params"], tiny["tokens"], tiny["labels"],
                           kept=tiny["own"]["kept"], **REF_KW)
    assert float(again["loss"]) == pytest.approx(float(tiny["own"]["loss"]),
                                                 abs=1e-6)


WRT = ["l0.attn.q.w", "l1.attn.k.w", "l2.attn.v.w", "l1.in_norm.w",
       "embed.w", "l1.index.q.w", "l2.index.w.w"]


# its own body: a fault may show in the kept sets alone (comparison a), or as
# a gradient that reaches the indexer
@pytest.mark.parametrize("fault", sorted(ref.FAULTS))
def test_each_planted_fault_is_refused(tiny, fault):
    """The two comparisons that pass the reference refuse each fault: its
    own selection keeps other pairs than the system's (a), or under the
    system's kept sets its logits, its loss or a gradient move by far more
    than the system's distance from the true reference, or a gradient
    reaches the indexer (b)."""
    bad = ref.loss_parts(tiny["params"], tiny["tokens"], tiny["labels"],
                         return_kept=True, fault=fault, **REF_KW)
    agree = min(_agreement(tiny["got"][n], mine)
                if np.shape(mine) == np.shape(tiny["got"][n]) else 0.0
                for n, mine in zip(KEPT, bad["kept"]))
    handed, grads = ref.loss_and_grads(
        tiny["params"], tiny["tokens"], tiny["labels"], wrt=WRT, last=T,
        kept=[tiny["got"][n] for n in KEPT], fault=fault, **REF_KW)
    moved = [rel_err(tiny["got"]["logits"], handed["logits"])] + [
        frob(tiny["grads"][n], grads[n]) for n in WRT if n in tiny["grads"]]
    reaches = max(float(np.abs(np.asarray(grads[n])).max())
                  for n in WRT if n not in tiny["grads"])
    assert agree < 0.99 or max(moved) > 50 * 2e-4 or reaches > 0, \
        (fault, agree, moved, reaches)


def test_an_unknown_fault_is_refused(tiny):
    CASE.unknown_fault_is_refused(tiny)


def test_interpreted_kernels_give_the_reference_too(tiny, monkeypatch):
    """The same program with the index kernels and the `dsa_` flash kernels
    under the Pallas interpreter (at 256 tokens the one-pass forward and the
    fused backward) instead of the CPU path's jnp forms."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    _, params, feed, got, grads, _ = CASE.run_tiny(amp=False)
    for name in KEPT:
        assert _agreement(got[name], tiny["got"][name]) >= 0.9995
        assert np.array_equal(got[name].sum(-1)[0], ROW_KEEPS)
    want, want_grads = ref.loss_and_grads(
        params, jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"]),
        wrt=["l0.attn.q.w", "l1.attn.k.w", "l2.attn.v.w"], last=T,
        kept=[got[n] for n in KEPT], **REF_KW)
    assert rel_err(got["logits"], want["logits"]) < 1e-4
    for name, g in want_grads.items():
        assert frob(grads[name], g) < 2e-4, name


def test_topk_over_the_sequence_gives_the_causal_model(tiny):
    """`topk >= T`: every layer keeps the whole triangle and the model is
    the reference with no selection."""
    main, startup, fetches, _ = CASE.program(topk=T)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    for name, value in tiny["params"].items():
        scope.set_var(name, jnp.asarray(value))
    feed = {"tokens": np.asarray(tiny["tokens"]),
            "labels": np.asarray(tiny["labels"])}
    loss, kept = exe.run(main, feed=feed,
                         fetch_list=[fetches["loss"], fetches["l1.kept"]],
                         scope=scope)
    assert np.array_equal(kept[0], np.tril(np.ones((T, T), np.int8)))
    want = ref.loss_parts(tiny["params"], tiny["tokens"], tiny["labels"],
                          fault="no_selection", **REF_KW)
    assert float(loss[0]) == pytest.approx(float(want["loss"]), abs=2e-5)
    assert abs(float(want["loss"]) - float(tiny["want"]["loss"])) > 1e-4


# its own body: in blocks the reference selects again, so the kept sets are
# compared too, and the gradients only under the system's sets
def test_reference_in_blocks_is_the_reference(tiny):
    """`q_block` and `remat` are the reference's memory, not its
    mathematics."""
    parts, grads = ref.loss_and_grads(
        tiny["params"], tiny["tokens"], tiny["labels"],
        wrt=["l0.attn.q.w", "l2.attn.k.w", "l1.router.w", "embed.w"],
        q_block=64, remat=True, return_kept=True, **REF_KW)
    assert abs(float(parts["loss"]) - float(tiny["own"]["loss"])) < 1e-5
    for mine, whole in zip(parts["kept"], tiny["own"]["kept"]):
        assert _agreement(mine, whole) >= 0.9995
    handed, _ = ref.loss_and_grads(
        tiny["params"], tiny["tokens"], tiny["labels"], wrt=["embed.w"],
        q_block=64, kept=[tiny["got"][n] for n in KEPT], **REF_KW)
    assert abs(float(handed["loss"]) - float(tiny["want"]["loss"])) < 1e-5


# its own body: in bfloat16 the reference keeps other pairs, too
def test_reference_in_bfloat16_is_another_number(tiny):
    low = ref.loss_parts(tiny["params"], tiny["tokens"], tiny["labels"],
                         dtype=jnp.bfloat16, return_kept=True, **REF_KW)
    assert low["loss"].dtype == jnp.bfloat16
    assert abs(float(low["loss"]) - float(tiny["own"]["loss"])) > 1e-4
    assert min(_agreement(a, b) for a, b in zip(
        low["kept"], tiny["own"]["kept"])) < 0.9995


# its own body: the bf16 kept sets are compared first, and the reference is
# handed them
def test_tiny_model_amp_within_bf16_of_reference():
    """Under AMP the index products run in bf16: the kept sets differ from
    the float32 reference's at the threshold and nowhere else (every row
    still keeps exactly min(t + 1, topk)); under the system's own sets the
    loss, the logits and the gradients are within bf16 of the reference."""
    _, params, feed, got, grads, _ = CASE.run_tiny(amp=True, seeded=False)
    tokens, labels = jnp.asarray(feed["tokens"]), jnp.asarray(feed["labels"])
    own = ref.loss_parts(params, tokens, labels, return_kept=True, **REF_KW)
    for name, mine in zip(KEPT, own["kept"]):
        assert np.array_equal(got[name].sum(-1)[0], ROW_KEEPS)
        assert 0.9 < _agreement(got[name], mine) <= 1.0
    want, want_grads = ref.loss_and_grads(
        params, tokens, labels, last=T, kept=[got[n] for n in KEPT],
        **REF_KW)
    assert abs(float(got["loss"][0]) - float(want["loss"])) < 0.002
    assert got["logits"].dtype == jnp.bfloat16
    err = np.abs(np.asarray(got["logits"], np.float32)
                 - np.asarray(want["logits"]))
    std = float(np.std(want["logits"]))
    assert err.mean() < 0.02 * std and err.max() < 0.15 * std
    for name in ("l0.attn.q.w", "l0.attn.k.w", "l0.attn.v.w",
                 "l2.attn.q.w", "l1.experts.gate.w", "embed.w"):
        assert grads[name].dtype == np.float32
        limit = 0.08 if ".experts." in name else 0.04
        assert frob(grads[name], want_grads[name]) < limit, name


# -- what the loss cannot reach ------------------------------------------------------------

def test_a_step_leaves_the_indexer_bitwise_unchanged_and_without_moments():
    main, startup, fetches, _ = CASE.program(
        fluid.optimizer.Adam(learning_rate=3e-3))
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    names = TRAINED + FROZEN
    before = {n: np.asarray(scope.find_var(n)) for n in names}
    feed = CASE.batch()
    losses = [float(exe.run(main, feed=feed, fetch_list=[fetches["loss"]],
                            scope=scope)[0][0]) for _ in range(6)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    for name in names:
        same = np.array_equal(before[name], np.asarray(scope.find_var(name)))
        assert same == (name in FROZEN), name
    updated = {op.input("Param")[0] for op in main.global_block().ops
               if op.type == "adam"}
    assert updated == set(TRAINED)
    held = [n for n in scope.local_var_names() if ".index." in n]
    assert sorted(held) == sorted(FROZEN)       # no moment beside them
    block = main.global_block()
    assert not any(block.has_var(n + "@GRAD") for n in FROZEN)
    detail = observe.observatory().latest(main._uid).detail
    assert detail["frozen_parameters"] == len(FROZEN)


# -- spans and counters ---------------------------------------------------------------------

def test_compile_event_carries_the_census():
    assert int(ROW_KEEPS.sum()) == pa.kept_pairs(T, K) == 14368
    assert 0 == pa.interior_tiles(T)
    carries_the_census(CASE.compile_detail(), {
        "layer_kinds": {"sparse_attention": 3}, "dsa_layers": 3,
        "attention_rotary_layers": 3, "moe_experts_routed": 16,
        "moe_experts_held": 4,
        # batch 2 x 3 layers x (64 x 65 / 2 + 192 x 64) kept pairs
        "dsa_keys_kept": 2 * 3 * int(ROW_KEEPS.sum()),
        # batch 2 x 4 heads x 3 layers, one 256 x 256 tile a head
        "dsa_tiles_computed": 2 * 4 * 3 * pa.causal_tiles(T),
        # that one tile holds the diagonal: none runs without the causal mask
        "flash_tiles_unmasked": 0,
        # and its grid has no step above the diagonal to hold
        "flash_dead_steps_held": 0, "frozen_parameters": len(FROZEN)},
        absent=["window_tiles_computed"], startup_lacks=["dsa_layers"])


@pytest.mark.parametrize("seq,topk,pairs", [
    (8192, 2048, 14681088), (4096, 2048, 6292480), (2048, 2048, 2098176),
    (256, 64, 14368)])
def test_kept_pairs_by_closed_form(seq, topk, pairs):
    assert pa.kept_pairs(seq, topk) == pairs
    assert pa.kept_pairs(seq, topk) == int(
        np.minimum(np.arange(seq) + 1, topk).sum())


def test_causal_tiles_at_the_cells_length():
    assert pa._blk(8192, True) == (1024, 1024)
    assert pa.causal_tiles(8192) == 36
    # 28 of them lie wholly under the diagonal: the kept set alone masks them
    assert pa.interior_tiles(8192) == 28
    assert 4 * 32 * pa.interior_tiles(8192) == 3584     # the cell's tally
    # and 28 of the grid's 64 steps wholly above it: they fetch nothing
    assert 4 * 32 * pa._dead_steps(8192, 1024, 1024) == 3584


def test_the_unmasked_tally_follows_the_tiles(monkeypatch):
    """At tiles of 128 a head's triangle over 256 tokens is three tiles, of
    which the one under the diagonal runs without the causal mask, under the
    kept set alone: the counter is the forward ops', summed over the layers,
    and the grad ops' traces add nothing to it."""
    monkeypatch.setattr(pa, "_BLOCK_OVERRIDE", (128, 128))
    assert pa.causal_tiles(T) == 3 and pa.interior_tiles(T) == 1
    carries_the_census(CASE.compile_detail(), {
        "dsa_tiles_computed": 2 * 4 * 3 * 3,
        "flash_tiles_unmasked": 2 * 4 * 3 * 1,
        # one of the grid's four steps lies above the diagonal
        "flash_dead_steps_held": 2 * 4 * 3 * 1},
        startup_lacks=["flash_tiles_unmasked"])


def test_every_layer_is_built_under_its_name_scopes(tiny):
    mixer = ["fused_attention", "dsa_index_scores", "dsa_select",
             "layer_norm", "rotary_embedding", "expand", "rms_norm", "mul"]
    layers_are_built_under_their_scopes(
        tiny["main"], ["l0.dsa", "l1.dsa", "l2.dsa", "l0.moe", "l2.moe"],
        holds={"l0.dsa": mixer, "l2.dsa": mixer,
               "l1.moe": ["moe_router", "moe_dispatch", "grouped_matmul",
                          "moe_combine"]})


def test_attention_ops_take_the_kept_set():
    main, _, _, _ = CASE.program(n_layer=1)
    block = main.global_block()
    (op,) = [o for o in block.ops if o.type == "fused_attention"]
    (select,) = [o for o in block.ops if o.type == "dsa_select"]
    (scores,) = [o for o in block.ops if o.type == "dsa_index_scores"]
    assert op.input("Kept") == select.output("Kept")
    assert select.input("Scores") == scores.output("Scores")
    assert op.attrs["topk"] == select.attrs["topk"] == K
    assert "window" not in op.attrs
    assert scores.attrs["scale"] == pytest.approx(16 ** -0.5 * 4 ** -0.5)
    assert scores.attrs["tile"] == TINY["index_tile"]
    kept = block.var(op.input("Kept")[0])
    assert kept.shape[1:] == (T, T) and str(kept.dtype) == "int8"
    assert kept.stop_gradient
    grad = [o for o in block.ops if o.type == "fused_attention_grad"]
    assert len(grad) == 1
    assert not [o for o in block.ops
                if o.type in ("dsa_select_grad", "dsa_index_scores_grad")]


def test_amp_lists_leave_the_indexer_to_its_rules():
    """The index ops keep their own precision (bf16 products, float32
    elsewhere) inside their rules, as `rms_norm` does."""
    assert "fused_attention" in registry.AMP_BF16_OPS
    for op in ("dsa_index_scores", "dsa_select", "layer_norm"):
        assert op not in registry.AMP_F32_OPS | registry.AMP_BF16_OPS
    q, k, w = _index_operands(seed=6)
    low = sa.index_scores_xla(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                              w.astype(jnp.bfloat16), 0.125, 64)
    assert low.dtype == jnp.float32


def test_the_program_round_trips_with_its_new_ops_and_slot():
    main, _, fetches, _ = CASE.program(
        fluid.optimizer.Adam(learning_rate=1e-3), n_layer=1)
    parsed = fluid.Program.parse_from_string(main.serialize_to_string())
    (op,) = [o for o in parsed.global_block().ops
             if o.type == "fused_attention"]
    assert op.input("Kept") and op.attrs["topk"] == K
    assert [o.attrs["topk"] for o in parsed.global_block().ops
            if o.type == "dsa_select"] == [K]


# -- the files ------------------------------------------------------------------------------

def test_the_two_copies_of_the_reference_are_identical():
    CASE.two_copies_of_the_reference_are_identical()


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2048), ("num_attention_heads", 32),
    ("num_key_value_heads", 4), ("head_dim", 128),
    ("moe_intermediate_size", 768), ("num_experts_per_tok", 8),
    ("num_local_experts", 128), ("rope_theta", 10000000),
    ("num_hidden_layers", 4), ("num_experts", 8), ("vocab_size", 18992)])
def test_the_configuration_keeps_the_published_widths(key, value):
    assert CONFIG[key] == value
    assert CONFIG["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    args = CONFIG["build_args"]
    assert (args["d_model"], args["n_head"], args["n_kv_head"],
            args["head_dim"], args["d_expert"], args["top_k"],
            args["n_expert"], args["experts_held"]) == (
                2048, 32, 4, 128, 768, 8, 128, 8)
    assert (args["n_index_head"], args["index_dim"], args["topk"],
            args["index_tile"]) == (16, 64, 2048, 512)


def test_the_tiny_block_runs_through_the_benchmark():
    runs_through_the_benchmark("keye_vl_2_30b_a3b.s8192")
