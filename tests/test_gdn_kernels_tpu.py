"""TPU-only: the gated delta rule's Mosaic kernels (`gdn_fwd`, `gdn_bwd`,
`ops/linear_attention.py`) at the shapes of `qwen3_next_80b_a3b.bs1`,
q and k `[1, 4096, 16, 128]`, v `[1, 4096, 32, 128]` in bf16, against
`jax.vjp` of the XLA form. The CPU suite holds the kernels to both oracles
under the Pallas interpreter in float32 (`tests/test_gdn_kernels.py`); what
only the chip can say is that Mosaic compiles them, that their HIGHEST
products are float32 there, and that their one-pass products read no worse
than XLA's at its default precision. Since PR 64 also at the shapes of
`olmo_hybrid_7b.s4096`, heads of 96 / 192 filled out to 128 / 256 lanes
(`readings_off_tile`), to the same tolerance for the same reason: the filled
channels are exact zeros on both sides of every product, so the pair computes
the products it computes at 128 / 128, each rounded as XLA's default rounds
it."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import linear_attention as la

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Mosaic kernels need real TPU hardware")

B, T, HK, HV, D, CHUNK = 1, 4096, 16, 32, 128, 64
SLOTS = "q k v g beta".split()


def _frob(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30)


def _xla_form(q, k, v, g, beta):
    r = v.shape[2] // q.shape[2]
    q = la.l2_normalize(q.astype(jnp.float32)) * q.shape[3] ** -0.5
    k = la.l2_normalize(k.astype(jnp.float32))
    q, k = (jnp.repeat(x, r, axis=2) for x in (q, k))
    return la.chunked_gated_delta_rule(q, k, v.astype(jnp.float32), g, beta,
                                       CHUNK)


@jax.jit
def _xla_value_and_grads(q, k, v, g, beta, d_out):
    out, vjp = jax.vjp(_xla_form, q, k, v, g, beta)
    return out, vjp(d_out.astype(jnp.float32))


def _read(hk, hv, dk, dv, beta_over=None):
    rng = np.random.RandomState(0)
    bf16 = jnp.bfloat16
    q = jnp.asarray(rng.randn(B, T, hk, dk), bf16)
    k = jnp.asarray(rng.randn(B, T, hk, dk), bf16)
    v = jnp.asarray(rng.randn(B, T, hv, dv), bf16)
    g = jnp.asarray(-np.exp(rng.uniform(-1, 2.5, hv))
                    * np.log1p(np.exp(rng.randn(B, T, hv))), jnp.float32)
    beta = 1 / (1 + np.exp(-rng.randn(B, T, hv)))
    if beta_over:
        beta = rng.uniform(*beta_over, (B, T, hv))
    beta = jnp.asarray(beta, jnp.float32)
    d_out = jnp.asarray(rng.randn(B, T, hv, dv), bf16)
    args = (q, k, v, g, beta)
    out, states = jax.jit(lambda *a: la._gdn_forward(*a, CHUNK))(*args)
    grads = jax.jit(lambda *a: la._gdn_backward(*a, CHUNK))(
        *args, states, d_out)
    xla = _xla_value_and_grads(*args, d_out)
    with jax.default_matmul_precision("highest"):
        exact = _xla_value_and_grads(*args, d_out)
    return dict(out=out, states=states, grads=grads, xla=xla, exact=exact)


@pytest.fixture(scope="module")
def readings():
    return _read(HK, HV, D, D)


@pytest.fixture(scope="module")
def readings_off_tile():
    """Olmo-Hybrid's rule: 15 heads of 96 / 192, beta over (0, 2)."""
    return _read(15, 15, 96, 192, beta_over=(0.05, 1.95))


def test_outputs_keep_their_inputs_shapes_and_dtypes(readings):
    assert readings["out"].shape == (B, T, HV, D)
    assert readings["out"].dtype == jnp.bfloat16
    assert readings["states"].shape == (T // CHUNK, B, HV, D, D)
    assert readings["states"].dtype == jnp.float32
    dq, dk, dv, dg, dbeta = readings["grads"]
    assert dq.shape == dk.shape == (B, T, HK, D) and dq.dtype == jnp.bfloat16
    assert dv.shape == (B, T, HV, D) and dv.dtype == jnp.bfloat16
    assert dg.shape == dbeta.shape == (B, T, HV) and dg.dtype == jnp.float32


def test_forward_reads_no_worse_than_xla_at_default_precision(readings):
    """Both sides against the XLA form at HIGHEST: the kernels' one-pass
    products are XLA's default ones, so neither reads far from the other
    (bf16 rounding of the output is in both)."""
    exact = readings["exact"][0]
    kernel, xla = _frob(readings["out"], exact), _frob(readings["xla"][0],
                                                       exact)
    assert np.all(np.isfinite(np.asarray(readings["out"], np.float32)))
    assert kernel < 1.5 * xla + 1e-3, (kernel, xla)
    assert kernel < 0.01


@pytest.mark.parametrize("slot", range(5), ids=SLOTS)
def test_gradient_reads_no_worse_than_xla_at_default_precision(readings,
                                                               slot):
    exact = readings["exact"][1][slot]
    got = np.asarray(readings["grads"][slot], np.float32)
    assert np.all(np.isfinite(got))
    kernel = _frob(got, exact)
    xla = _frob(readings["xla"][1][slot], exact)
    assert kernel < 1.5 * xla + 1e-3, (SLOTS[slot], kernel, xla)
    assert kernel < 0.01


def test_off_the_lane_tile_outputs_keep_the_given_shapes(readings_off_tile):
    assert la._plan(96, 192, CHUNK, T // CHUNK) == ("kernel", 2)
    r = readings_off_tile
    assert r["out"].shape == (B, T, 15, 192) and r["out"].dtype == jnp.bfloat16
    assert r["states"].shape == (T // CHUNK, B, 15, 96, 192)
    dq, dk, dv, dg, dbeta = r["grads"]
    assert dq.shape == dk.shape == (B, T, 15, 96) and dq.dtype == jnp.bfloat16
    assert dv.shape == (B, T, 15, 192) and dv.dtype == jnp.bfloat16
    assert dg.shape == dbeta.shape == (B, T, 15) and dg.dtype == jnp.float32


@pytest.mark.parametrize("slot", range(6), ids=["out"] + SLOTS)
def test_off_the_lane_tile_reads_no_worse_than_xla_at_default_precision(
        readings_off_tile, slot):
    """`Out` and the five gradients of the filled-out pair against the XLA
    form at HIGHEST, beside the XLA form at its default: the tolerance of
    the 128 / 128 tests, for their reason (the filled channels are zeros in
    every product, so no product rounds otherwise than there)."""
    r = readings_off_tile
    got, xla, exact = ((r["out"], r["xla"][0], r["exact"][0]) if slot == 0
                       else (r["grads"][slot - 1], r["xla"][1][slot - 1],
                             r["exact"][1][slot - 1]))
    got = np.asarray(got, np.float32)
    assert np.all(np.isfinite(got))
    kernel, xla = _frob(got, exact), _frob(xla, exact)
    assert kernel < 1.5 * xla + 1e-3, (slot, kernel, xla)
    assert kernel < 0.01
