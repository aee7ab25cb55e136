"""TPU-only: the rotary op's Mosaic kernel (`rotary_fwd`, `rotary_bwd`,
`ops/decoder_block.py`) at the shapes of `mellum2_12b_a2_5b.s8192` (q of 32
heads and k of 4, 8192 tokens, plain and YaRN's tables) and of
`kanana_2_30b_a3b.bs1` (interleaved pairs at a head of 64, q of 32 heads and
the one key head), bf16, against the XLA form and its `jax.vjp`. The CPU
suite holds the kernel to the same oracle under the Pallas interpreter
(`tests/test_rotary_kernels.py`); what only the chip can say is that the
lane rotation and the products with the 0 / +-1 matrices give the bits the
XLA form gives."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import models
from paddle_tpu.ops import decoder_block as db

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Mosaic kernels need real TPU hardware")

CASES = {
    "mellum2_q": ((1, 32, 8192, 128), False, None),
    "mellum2_k_yarn": ((1, 4, 8192, 128), False, models.mellum2.YARN),
    "kanana2_q": ((1, 32, 4096, 64), True, None),
    "kanana2_k": ((1, 1, 4096, 64), True, None),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def readings(request):
    shape, interleaved, scaling = CASES[request.param]
    T, D = shape[-2:]
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    g = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    assert db._rotary_plan(shape, x.dtype, D, interleaved) != "xla"

    def tables():
        return db.rotary_tables(T, *db.rotary_frequencies(D, 1e4, scaling))

    @jax.jit
    def kernel(x, g):
        cos, sin = tables()
        return (db._rotary_call(x, cos, sin, interleaved, False),
                db._rotary_call(g, cos, sin, interleaved, True))

    @jax.jit
    def xla(x, g):
        cos, sin = tables()
        want, vjp = jax.vjp(
            lambda x: db._rotary_xla(x, D, interleaved, cos, sin), x)
        return want, vjp(g)[0]

    return x, kernel(x, g), xla(x, g)


def test_outputs_keep_their_inputs_shape_and_dtype(readings):
    x, got, _ = readings
    for a in got:
        assert a.shape == x.shape and a.dtype == x.dtype


@pytest.mark.parametrize("which", [0, 1], ids=["out", "dx"])
def test_the_pass_agrees_with_the_xla_form_to_bf16s_rounding(readings,
                                                             which):
    """Both multiply and add in float32 against the same tables and round
    once: an element differs by a last bit of bf16 at most, and few do (the
    two compilers fuse the multiply into the add differently)."""
    _, got, want = readings
    got = np.asarray(got[which], np.float32)
    want = np.asarray(want[which], np.float32)
    assert np.all(np.isfinite(got)) and np.any(got)
    assert np.all(np.abs(got - want) <= 2.0 ** -7 * np.abs(want) + 1e-6)
    assert np.mean(got != want) < 0.01
