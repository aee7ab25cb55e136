"""TPU-only: the paged decode-read kernels as Mosaic compiles them (the
CPU suite runs them under the interpreter, tests/test_decode.py). Float32
and int8 residency against their references at chip-shaped cache
geometries, ragged lengths included, and the registered decode op end to
end on a cache that takes appends."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import paged_attention as pa

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="Mosaic-compiled paged attention needs real TPU hardware")

# outputs are O(1); the reference runs at "highest" matmul precision
ATOL = 2e-2


def _cache(S, H, Dh, bs, max_b, seed=0):
    rng = np.random.RandomState(seed)
    nblk = 1 + S * max_b
    q = jnp.asarray(rng.randn(S, H, Dh), jnp.float32)
    kc = jnp.asarray(rng.randn(nblk, bs, H, Dh), jnp.float32)
    vc = jnp.asarray(rng.randn(nblk, bs, H, Dh), jnp.float32)
    # shuffled block ownership: the table, not adjacency, orders a sequence
    ids = 1 + rng.permutation(S * max_b).reshape(S, max_b)
    bt = jnp.asarray(ids, jnp.int32)
    lens = ([max_b * bs, 0, bs + 3, 1, 2 * bs, bs - 1] * S)[:S]
    return q, kc, vc, bt, jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("S,H,Dh,bs,max_b", [(4, 8, 128, 16, 64),
                                             (8, 16, 128, 32, 16),
                                             (2, 4, 256, 8, 8)])
def test_float32_kernel_matches_reference(S, H, Dh, bs, max_b):
    q, kc, vc, bt, seq = _cache(S, H, Dh, bs, max_b)
    sm = Dh ** -0.5
    with jax.default_matmul_precision("highest"):
        ref = pa.paged_attention_reference(q, kc, vc, bt, seq, sm)
    ker = jax.jit(pa._paged_attention_pallas, static_argnums=5)(
        q, kc, vc, bt, seq, sm)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=ATOL)
    assert not np.asarray(ker)[1].any()          # inactive slot: zeros


@pytest.mark.parametrize("S,H,Dh,bs,max_b", [(4, 8, 128, 16, 64),
                                             (8, 16, 128, 32, 16)])
def test_int8_kernel_matches_reference(S, H, Dh, bs, max_b):
    q, kc, vc, bt, seq = _cache(S, H, Dh, bs, max_b, seed=1)
    sm = Dh ** -0.5
    ks = jnp.max(jnp.abs(kc), axis=(1, 2, 3)) / 127.0
    vs = jnp.max(jnp.abs(vc), axis=(1, 2, 3)) / 127.0
    kq = jnp.rint(kc / ks[:, None, None, None]).astype(jnp.int8)
    vq = jnp.rint(vc / vs[:, None, None, None]).astype(jnp.int8)
    with jax.default_matmul_precision("highest"):
        ref = pa.paged_attention_q8_reference(q, kq, vq, ks, vs, bt, seq, sm)
    ker = jax.jit(pa._paged_attention_q8_pallas, static_argnums=7)(
        q, kq, vq, ks, vs, bt, seq, sm)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=ATOL)
    assert not np.asarray(ker)[1].any()


def test_geometry_outside_the_envelope_raises():
    q, kc, vc, bt, seq = _cache(4, 2, 8, 4, 4)    # tiny_lm's CPU signature
    with pytest.raises(ValueError, match="head_dim"):
        pa.paged_attention(q, kc, vc, bt, seq)


def test_public_entry_takes_the_kernel_and_appends_compose():
    """paged_attention() after kv_cache_append, jitted together as the
    decode op does: the new token's K/V is visible to the read."""
    S, H, Dh, bs, max_b = 4, 8, 128, 16, 8
    q, kc, vc, bt, _ = _cache(S, H, Dh, bs, max_b, seed=2)
    seq = jnp.asarray([bs + 1, 0, 5, 1], jnp.int32)
    rng = np.random.RandomState(3)
    k_new = jnp.asarray(rng.randn(S, H, Dh), jnp.float32)
    v_new = jnp.asarray(rng.randn(S, H, Dh), jnp.float32)

    def step(read):
        def f(q, kc, vc, k_new, v_new, bt, seq):
            kc2, vc2 = pa.kv_cache_append(kc, vc, k_new, v_new, bt, seq)
            return read(q, kc2, vc2, bt, seq, Dh ** -0.5)
        return jax.jit(f)(q, kc, vc, k_new, v_new, bt, seq)

    with jax.default_matmul_precision("highest"):
        ref = step(pa.paged_attention_reference)
    ker = step(pa.paged_attention)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=ATOL)
