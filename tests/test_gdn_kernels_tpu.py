"""TPU-only: the gated delta rule's Mosaic kernels (`gdn_fwd`, `gdn_bwd`,
`ops/linear_attention.py`) at the shapes of `qwen3_next_80b_a3b.bs1`,
q and k `[1, 4096, 16, 128]`, v `[1, 4096, 32, 128]` in bf16, against
`jax.vjp` of the XLA form. The CPU suite holds the kernels to both oracles
under the Pallas interpreter in float32 (`tests/test_gdn_kernels.py`); what
only the chip can say is that Mosaic compiles them, that their HIGHEST
products are float32 there, and that their one-pass products read no worse
than XLA's at its default precision."""

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
    q = la.l2_normalize(q.astype(jnp.float32)) * D ** -0.5
    k = la.l2_normalize(k.astype(jnp.float32))
    q, k = (jnp.repeat(x, HV // HK, axis=2) for x in (q, k))
    return la.chunked_gated_delta_rule(q, k, v.astype(jnp.float32), g, beta,
                                       CHUNK)


@jax.jit
def _xla_value_and_grads(q, k, v, g, beta, d_out):
    out, vjp = jax.vjp(_xla_form, q, k, v, g, beta)
    return out, vjp(d_out.astype(jnp.float32))


@pytest.fixture(scope="module")
def readings():
    rng = np.random.RandomState(0)
    bf16 = jnp.bfloat16
    q = jnp.asarray(rng.randn(B, T, HK, D), bf16)
    k = jnp.asarray(rng.randn(B, T, HK, D), bf16)
    v = jnp.asarray(rng.randn(B, T, HV, D), bf16)
    g = jnp.asarray(-np.exp(rng.uniform(-1, 2.5, HV))
                    * np.log1p(np.exp(rng.randn(B, T, HV))), jnp.float32)
    beta = jnp.asarray(1 / (1 + np.exp(-rng.randn(B, T, HV))), jnp.float32)
    d_out = jnp.asarray(rng.randn(B, T, HV, D), bf16)
    args = (q, k, v, g, beta)
    out, states = jax.jit(lambda *a: la._gdn_forward(*a, CHUNK))(*args)
    grads = jax.jit(lambda *a: la._gdn_backward(*a, CHUNK))(
        *args, states, d_out)
    xla = _xla_value_and_grads(*args, d_out)
    with jax.default_matmul_precision("highest"):
        exact = _xla_value_and_grads(*args, d_out)
    return dict(out=out, states=states, grads=grads, xla=xla, exact=exact)


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
