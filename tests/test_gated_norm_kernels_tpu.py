"""TPU-only: the gated norm's Mosaic kernels (`gated_norm_fwd`,
`gated_norm_bwd`, `ops/decoder_block.py`) at the shape of
`qwen3_next_80b_a3b.bs1` (32 value heads of 128, 4096 tokens, bf16; float32
too) against the XLA form and its `jax.vjp`. The CPU suite holds the kernels
to the same oracle under the Pallas interpreter
(`tests/test_gated_norm_kernels.py`), where Y comes out bitwise; on the chip
XLA is allowed more precision than the rule asks for and keeps the normed
value in float32 where the rule (and the kernel) rounds it to X's dtype, so
in bf16 the two differ by that rounding."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import decoder_block as db

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Mosaic kernels need real TPU hardware")

SHAPE = (1, 4096, 32, 128)
EPS = 1e-6


@pytest.fixture(scope="module", params=["bfloat16", "float32"])
def readings(request):
    dtype = jnp.dtype(request.param)
    rng = np.random.RandomState(0)
    x, gate, d_y = (jnp.asarray(scale * rng.randn(*SHAPE), dtype)
                    for scale in (2.0, 1.0, 1.0))
    w = jnp.asarray(rng.uniform(0.5, 1.5, SHAPE[-1]), jnp.float32)
    assert db._gated_norm_plan(SHAPE, dtype) == "kernel"

    @jax.jit
    def kernel(x, gate, w, d_y):
        return (db._gated_norm_call(x, gate, w, EPS),
                *db._gated_norm_call(x, gate, w, EPS, d_y))

    @jax.jit
    def xla(x, gate, w, d_y):
        want, vjp = jax.vjp(
            lambda *a: db._gated_norm_xla(*a, EPS), x, gate, w)
        return (want, *vjp(d_y))

    return request.param, x, kernel(x, gate, w, d_y), xla(x, gate, w, d_y)


def test_outputs_keep_their_operands_shapes_and_dtypes(readings):
    _, x, (y, dx, dgate, dw), _ = readings
    for a in (y, dx, dgate):
        assert a.shape == x.shape and a.dtype == x.dtype
    assert dw.shape == SHAPE[-1:] and dw.dtype == jnp.float32


@pytest.mark.parametrize("which", [0, 1, 2], ids=["y", "dx", "dgate"])
def test_a_pass_agrees_with_the_xla_form_to_its_dtypes_rounding(readings,
                                                                which):
    """Float32 inside on both sides; an element differs by the roundings of
    X's dtype (two in bf16: the normed value's and the result's)."""
    dtype, _, got, want = readings
    got = np.asarray(got[which], np.float32)
    want = np.asarray(want[which], np.float32)
    assert np.all(np.isfinite(got)) and np.any(got)
    ulp, floor = (2.0 ** -7, 2.0 ** -10) if dtype == "bfloat16" \
        else (2.0 ** -20, 1e-5)
    assert np.all(np.abs(got - want) <= 2 * ulp * np.abs(want) + floor)


def test_dscale_is_the_sum_over_tokens_and_heads(readings):
    """131072 rows summed in float32, eight sublanes of partial sums a grid
    step: against XLA's sum, which in bf16 sums terms whose normed factor
    was not rounded."""
    dtype, _, got, want = readings
    got, want = np.asarray(got[3]), np.asarray(want[3])
    rows = SHAPE[1] * SHAPE[2]
    assert np.max(np.abs(got - want)) <= (
        2.0 ** -8 * rows ** 0.5 * 8 if dtype == "bfloat16" else 1e-6 * rows)
