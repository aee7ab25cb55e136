"""TPU-only: the causal convolution's Mosaic kernels (`causal_conv_fwd`,
`causal_conv_bwd`, `ops/linear_attention.py`) at the shape of
`qwen3_next_80b_a3b.bs1`, X `[1, 4096, 8192]` in bf16 under a `[8192, 4]`
float32 weight, against the XLA form and its `jax.vjp`. The CPU suite holds
the kernels to the same oracle under the Pallas interpreter
(`tests/test_causal_conv_kernels.py`); what only the chip can say is that
Mosaic compiles them and that they round where the XLA form rounds."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import linear_attention as la

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Mosaic kernels need real TPU hardware")

B, T, C, K = 1, 4096, 8192, 4


def _frob(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30)


@pytest.fixture(scope="module")
def readings():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, T, C), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (C, K)), jnp.float32)
    d_out = jnp.asarray(rng.randn(B, T, C), jnp.bfloat16)
    assert la._conv_plan(T, C, K) == "kernel"
    out = jax.jit(lambda x, w: la._conv_forward(x, w, True))(x, w)
    dx, dw = jax.jit(lambda *a: la._conv_backward(*a, True))(x, w, d_out)

    @jax.jit
    def xla(x, w, d_out):
        want, vjp = jax.vjp(lambda x, w: la._conv_xla(x, w, True), x, w)
        return want, vjp(d_out)

    want, (dx_want, dw_want) = xla(x, w, d_out)
    return dict(out=out, dx=dx, dw=dw, want=want, dx_want=dx_want,
                dw_want=dw_want)


def test_outputs_keep_their_inputs_shapes_and_dtypes(readings):
    assert readings["out"].shape == readings["dx"].shape == (B, T, C)
    assert readings["out"].dtype == readings["dx"].dtype == jnp.bfloat16
    assert readings["dw"].shape == (C, K)
    assert readings["dw"].dtype == jnp.float32


@pytest.mark.parametrize("name", ["out", "dx"])
def test_the_passes_agree_with_the_xla_form_to_bf16s_rounding(readings,
                                                              name):
    """Both sum in float32 and round once, to bf16, at the end: an element
    differs by a last bit of bf16 at most (2^-7 relative; 1e-5 where a sum
    cancels), and few do."""
    got = np.asarray(readings[name], np.float32)
    want = np.asarray(readings["want" if name == "out" else "dx_want"],
                      np.float32)
    assert np.all(np.isfinite(got))
    assert np.all(np.abs(got - want) <= 2.0 ** -7 * np.abs(want) + 1e-5)
    assert _frob(got, want) < 1e-3


def test_the_weight_gradient_is_summed_in_float32(readings):
    got = np.asarray(readings["dw"])
    want = np.asarray(readings["dw_want"])
    assert np.all(np.isfinite(got))
    assert _frob(got, want) < 1e-3
    assert np.max(np.abs(got - want)) < 1e-3 * np.max(np.abs(want))
