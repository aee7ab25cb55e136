"""TPU-only: the selective scan's Mosaic kernels (`ssd_fwd`, `ssd_bwd`,
`ops/state_space.py`) at the shapes of the two cells that run them, x `[1,
2048, 64, 64]` in bf16 both: `nemotron_3_nano_30b_a3b.s2048`'s B and C `[1,
2048, 8, 128]` at chunk 128 (a head block a group) and
`granite_4_0_h_micro.s2048`'s `[1, 2048, 1, 128]` at chunk 256 (ONE group in
eight head blocks, steps of 128 tokens inside, dB and dC summed over the
blocks), against `jax.vjp` of the XLA form at the cell's chunk; beside them
the two kernel pairs Nemotron's configuration calls in a form of its own:
the causal convolution with a bias at `[1, 2048, 6144]` and the gated norm
with the gate first over 8 groups of 512. The CPU suite holds all of them to
their jnp forms under the Pallas interpreter in float32
(`tests/test_nemotron_h.py`, `tests/test_granite_hybrid.py`); what only the
chip can say is that their one-pass products read no worse than XLA's at its
default precision."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import decoder_block as db
from paddle_tpu.ops import linear_attention as la
from paddle_tpu.ops import state_space as ss

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Mosaic kernels need real TPU hardware")

B, T, H, P, N = 1, 2048, 64, 64, 128
CELLS = {"nemotron": (8, 128), "granite": (1, 256)}     # groups, chunk
SLOTS = list(ss._SLOTS)


def _frob(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30)


@functools.partial(jax.jit, static_argnums=2)
def _xla_value_and_grads(args, d_out, chunk):
    out, vjp = jax.vjp(lambda *v: ss.chunked_ssd(
        *(a.astype(jnp.float32) for a in v), chunk), *args)
    return out, vjp(d_out.astype(jnp.float32))


@pytest.fixture(scope="module", params=sorted(CELLS))
def readings(request):
    G, CHUNK = CELLS[request.param]
    assert ss._plan(P, N, H // G, CHUNK) == "kernel"
    rng = np.random.RandomState(0)
    bf16 = jnp.bfloat16
    x = jnp.asarray(rng.randn(B, T, H, P), bf16)
    b = jnp.asarray(rng.randn(B, T, G, N) * 0.3, bf16)
    c = jnp.asarray(rng.randn(B, T, G, N) * 0.3, bf16)
    # step sizes as the public initialisation draws them, decays 1..64
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                        (B, T, H))), jnp.float32)
    a = -jnp.arange(1, H + 1, dtype=jnp.float32) * dt
    skip = jnp.asarray(rng.uniform(0.5, 1.5, H), jnp.float32)
    d_out = jnp.asarray(rng.randn(B, T, H, P), bf16)
    args = (x, dt, a, b, c, skip)
    out, states = jax.jit(lambda *v: ss._ssd_forward(*v, CHUNK))(*args)
    grads = jax.jit(lambda *v: ss._ssd_backward(*v, CHUNK))(
        *args, states, d_out)
    xla = _xla_value_and_grads(args, d_out, CHUNK)
    with jax.default_matmul_precision("highest"):
        exact = _xla_value_and_grads(args, d_out, CHUNK)
    return dict(out=out, states=states, grads=grads, xla=xla, exact=exact,
                groups=G)


def test_outputs_keep_their_inputs_shapes_and_dtypes(readings):
    assert readings["out"].shape == (B, T, H, P)
    assert readings["out"].dtype == jnp.bfloat16
    assert readings["states"].shape == (T // 128, B, H, P, N)
    assert readings["states"].dtype == jnp.float32
    dx, ddt, da, d_b, d_c, d_skip = readings["grads"]
    assert dx.shape == (B, T, H, P) and dx.dtype == jnp.bfloat16
    assert d_b.shape == d_c.shape == (B, T, readings["groups"], N)
    assert d_b.dtype == d_c.dtype == jnp.bfloat16
    assert ddt.shape == da.shape == (B, T, H) and da.dtype == jnp.float32
    assert d_skip.shape == (H,)


def test_forward_reads_no_worse_than_xla_at_default_precision(readings):
    """Both sides against the XLA form at HIGHEST. The kernel writes y in
    x's bf16, the XLA form returns float32, so the XLA form's output is
    rounded to bf16 here before it is read: the kernels' one-pass products
    are XLA's default ones, and neither reads far from the other (first
    reading, PR 56: kernel 0.00166 with its rounding, the XLA form 0.00009
    without)."""
    exact = readings["exact"][0]
    rounded = readings["xla"][0].astype(jnp.bfloat16)
    kernel, xla = _frob(readings["out"], exact), _frob(rounded, exact)
    assert np.all(np.isfinite(np.asarray(readings["out"], np.float32)))
    print(f"ssd forward: kernel {kernel:.5f}, xla default {xla:.5f}")
    assert kernel < 1.5 * xla + 1e-3, (kernel, xla)
    assert kernel < 0.01


@pytest.mark.parametrize("slot", range(6), ids=SLOTS)
def test_gradient_reads_no_worse_than_xla_at_default_precision(readings,
                                                               slot):
    exact = readings["exact"][1][slot]
    got = np.asarray(readings["grads"][slot], np.float32)
    assert np.all(np.isfinite(got))
    kernel = _frob(got, exact)
    xla = _frob(readings["xla"][1][slot], exact)
    print(f"ssd gradient of {SLOTS[slot]}: kernel {kernel:.5f}, xla default "
          f"{xla:.5f}")
    assert kernel < 1.5 * xla + 1e-3, (SLOTS[slot], kernel, xla)
    assert kernel < 0.02


def test_causal_conv_with_a_bias_at_the_cells_shape():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(1, T, 6144), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (6144, 4)), jnp.float32)
    bias = jnp.asarray(rng.randn(6144) * 0.3, jnp.float32)
    d_out = jnp.asarray(rng.randn(1, T, 6144), jnp.bfloat16)
    out = jax.jit(lambda *a: la._conv_forward(*a, True, bias))(x, w)
    dx, dw, d_bias = jax.jit(lambda *a: la._conv_backward(*a, True, bias))(
        x, w, d_out)
    want, vjp = jax.vjp(lambda x, w, b: la._conv_xla(x, w, True, b), x, w,
                        bias)
    assert _frob(out, want) < 0.01
    for got, g in zip((dx, dw, d_bias), vjp(d_out)):
        assert _frob(got, g) < 0.01


def test_gate_first_grouped_norm_at_the_cells_shape():
    rng = np.random.RandomState(2)
    shape = (1, T, 8, 512)
    x = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    z = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(0.5, 1.5, 4096), jnp.float32)
    d_y = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    y = jax.jit(lambda *a: db._gate_first_norm_call(*a, 1e-5))(x, z, w)
    grads = jax.jit(lambda *a: db._gate_first_norm_call(*a[:3], 1e-5, a[3]))(
        x, z, w, d_y)
    want, vjp = jax.vjp(lambda *a: db._gate_first_norm_xla(*a, 1e-5), x, z, w)
    assert _frob(y, want) < 0.01
    for got, g in zip(grads, vjp(d_y)):
        assert _frob(got, g) < 0.02
