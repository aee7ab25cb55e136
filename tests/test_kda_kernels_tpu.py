"""TPU-only: the per-channel delta rule's Mosaic kernels (`kda_fwd`,
`kda_bwd`, `ops/linear_attention.py`) at the shapes of
`ling_3_0_flash_vl.s2048`, q, k, v `[1, 2048, 32, 128]` in bf16, g float32
`[1, 2048, 32, 128]`, against `jax.vjp` of the XLA form `chunked_kda_rule`.
The CPU suite holds the kernels to both oracles under the Pallas interpreter
in float32 (`tests/test_kda_kernels.py`); what only the chip can say is that
Mosaic compiles them, that their HIGHEST products are float32 there, and that
their one-pass products read no worse than XLA's at its default precision."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import linear_attention as la

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Mosaic kernels need real TPU hardware")

B, T, H, D, CHUNK = 1, 2048, 32, 128, 64
SLOTS = "q k v g beta".split()
DECAYS = ["whole_range", "minus_5_everywhere", "near_0"]


def _frob(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30)


@jax.jit
def _xla_value_and_grads(q, k, v, g, beta, d_out):
    out, vjp = jax.vjp(lambda *a: la._kda_rule(*a, CHUNK), q, k, v, g, beta)
    return out, vjp(d_out)


@pytest.fixture(scope="module", params=DECAYS)
def readings(request):
    rng = np.random.RandomState(0)
    bf16 = jnp.bfloat16
    q, k, v, d_out = (jnp.asarray(rng.randn(B, T, H, D), bf16)
                      for _ in range(4))
    g = {"whole_range": -5.0 * rng.uniform(0, 1, (B, T, H, D)),
         "minus_5_everywhere": np.full((B, T, H, D), -5.0),
         "near_0": -1e-3 * rng.uniform(0, 1, (B, T, H, D))}[request.param]
    g = jnp.asarray(g, jnp.float32)
    beta = jnp.asarray(1 / (1 + np.exp(-rng.randn(B, T, H))), jnp.float32)
    args = (q, k, v, g, beta)
    out, states = jax.jit(lambda *a: la._kda_forward(*a, CHUNK))(*args)
    grads = jax.jit(lambda *a: la._kda_backward(*a, CHUNK))(
        *args, states, d_out)
    xla = _xla_value_and_grads(*args, d_out)
    with jax.default_matmul_precision("highest"):
        exact = _xla_value_and_grads(*args, d_out)
    return dict(out=out, states=states, grads=grads, xla=xla, exact=exact,
                decay=request.param)


def test_outputs_keep_their_inputs_shapes_and_dtypes(readings):
    assert readings["out"].shape == (B, T, H, D)
    assert readings["out"].dtype == jnp.bfloat16
    assert readings["states"].shape == (T // CHUNK, B, H, D, D)
    assert readings["states"].dtype == jnp.float32
    dq, dk, dv, dg, dbeta = readings["grads"]
    assert dq.shape == dk.shape == dv.shape == dg.shape == (B, T, H, D)
    assert dq.dtype == dk.dtype == dv.dtype == jnp.bfloat16
    assert dbeta.shape == (B, T, H)
    assert dg.dtype == dbeta.dtype == jnp.float32


def test_forward_reads_no_worse_than_xla_at_default_precision(readings):
    """Both sides against the XLA form at HIGHEST: the kernels' one-pass
    products are XLA's default ones (operands rounded to bf16, float32
    sums), so neither reads far from the other; the output's rounding to
    bf16 is in both."""
    exact = readings["exact"][0]
    kernel, xla = _frob(readings["out"], exact), _frob(readings["xla"][0],
                                                       exact)
    assert np.all(np.isfinite(np.asarray(readings["out"], np.float32)))
    assert kernel < 1.5 * xla + 1e-3, (kernel, xla)
    assert kernel < 0.01


# What a gradient may read against the XLA form at HIGHEST, beside the half
# again of XLA's own default-precision reading that every case is held to.
# 1% like the scalar rule's pair, but for g: dG is a difference of two sums of
# one-pass products, `x dx - k dk`, and at a live decay what is left of it is
# small beside either: over the whole range the XLA form at its default reads
# 0.0405 and the kernels 0.0405 (my chip run, PR 61, call 3); at g = -5
# everywhere the exact gradient's norm is 0.08 where it is 4.3 over the whole
# range and 1734 near 0, and both sides read 2.0: rounding alone, so only the
# bound against XLA's own reading holds there.
LIMITS = {("g", "whole_range"): 0.06, ("g", "minus_5_everywhere"): None}


@pytest.mark.parametrize("slot", range(5), ids=SLOTS)
def test_gradient_reads_no_worse_than_xla_at_default_precision(readings,
                                                               slot):
    exact = readings["exact"][1][slot]
    got = np.asarray(readings["grads"][slot], np.float32)
    assert np.all(np.isfinite(got))
    kernel = _frob(got, exact)
    xla = _frob(readings["xla"][1][slot], exact)
    assert kernel < 1.5 * xla + 1e-3, (SLOTS[slot], kernel, xla)
    limit = LIMITS.get((SLOTS[slot], readings["decay"]), 0.01)
    assert limit is None or kernel < limit, (SLOTS[slot], kernel, limit)
