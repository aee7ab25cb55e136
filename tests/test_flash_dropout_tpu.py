"""TPU-only: in-kernel flash-attention dropout numerical verification
(VERDICT r2 weak #3 — the hash-seeded mask consistency across the fwd,
dQ and dK/dV kernels is unverifiable under the CPU interpreter because
pltpu.prng_* has no interpreter implementation).

The decisive check is directional finite differences under a FIXED seed:
the FD probe evaluates the FORWARD kernel twice while the analytic grad
comes from the BACKWARD kernels — they only agree if all three kernels
regenerate the identical keep-mask from (seed, tile index)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _fd_precision():
    """FD probes against bf16-default TPU matmuls read ~5x off; raise the
    precision for THIS file only and restore it after (a module-level
    config.update would leak into every other collected test)."""
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", prev)

from paddle_tpu.ops.pallas_attention import flash_attention

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="in-kernel PRNG dropout only runs on real TPU hardware")


def _setup(rate, seed=7, T=128):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 2, T, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, T, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, T, 64).astype(np.float32))
    s = jnp.int32(seed)

    def loss(q_, k_, v_):
        return flash_attention(q_, k_, v_, s, False, 0.125, rate).sum()

    return q, k, v, loss


def test_dropout_deterministic_per_seed():
    q, k, v, _ = _setup(0.3)
    a = flash_attention(q, k, v, jnp.int32(7), False, 0.125, 0.3)
    b = flash_attention(q, k, v, jnp.int32(7), False, 0.125, 0.3)
    c = flash_attention(q, k, v, jnp.int32(8), False, 0.125, 0.3)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_dropout_keep_rate():
    """E[dropout(out)] tracks the no-dropout output (upscale preserves the
    mean), and dropping actually happens (outputs differ)."""
    q, k, v, _ = _setup(0.3)
    ref = np.asarray(flash_attention(q, k, v, jnp.int32(0), False, 0.125,
                                     0.0))
    outs = [np.asarray(flash_attention(q, k, v, jnp.int32(s), False, 0.125,
                                       0.3)) for s in range(8)]
    assert not np.array_equal(outs[0], ref)
    mean = np.mean(outs, axis=0)
    # averaged over seeds the upscaled-dropout output approaches ref
    err = np.abs(mean - ref).mean() / (np.abs(ref).mean() + 1e-6)
    assert err < 0.25, err


@pytest.mark.parametrize("T,tiles", [(128, None), (512, (128, 128))],
                         ids=["one_block_a_row", "4x4_blocks"])
@pytest.mark.parametrize("rate", [0.0, 0.3])
def test_fwd_bwd_masks_agree_via_directional_fd(monkeypatch, rate, T, tiles):
    """grad . v == (loss(x+eps v) - loss(x-eps v)) / 2eps for random
    directions v — only true if the backward kernel regenerates the
    forward's dropout mask exactly, tile by tile. `4x4_blocks`: the fused
    kernel visits the tiles k-block first, the forward q-block first."""
    from paddle_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "_BLOCK_OVERRIDE", tiles)
    q, k, v, loss = _setup(rate, T=T)
    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    rng = np.random.RandomState(3)
    eps = 1e-2
    for arg in range(3):
        args = [q, k, v]
        d = jnp.asarray(rng.randn(*args[arg].shape).astype(np.float32))
        args_p = list(args); args_p[arg] = args[arg] + eps * d
        args_m = list(args); args_m[arg] = args[arg] - eps * d
        fd = (float(loss(*args_p)) - float(loss(*args_m))) / (2 * eps)
        an = float(jnp.vdot(g[arg], d))
        np.testing.assert_allclose(an, fd, rtol=5e-2, atol=2.0,
                                   err_msg=f"arg={arg} rate={rate}")
