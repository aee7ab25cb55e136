"""Pallas TPU dropout: in-kernel PRNG, mask regenerated in backward.

The reference dropout kernel (operators/dropout_op.cu) draws from a cuRAND
Philox stream and stores the mask tensor for the backward pass. On TPU the
expensive parts are (a) generating random bits through XLA's RNG (a long
integer-op chain on the VPU that cannot ride the MXU) and (b) a full
mask-tensor round trip through HBM. This kernel sidesteps both: each tile
seeds the hardware PRNG from (step_seed, tile_index) and draws its bits in
VMEM, and the backward kernel re-derives the identical mask from the same
seed instead of loading a stored one — dropout becomes a pure
read-x/write-y elementwise pass at HBM speed.

Same tile-hash re-seeding scheme as ops/pallas_attention.py so masks are
independent of grid iteration order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .pallas_attention import _HASH_A, _HASH_B

_LANES = 128
# target elements per grid step (~512 KB bf16 blocks)
_BLOCK_ELEMS = 2048 * 128


def _mask_for_tile(seed_ref, tile_idx, shape, rate):
    from jax.experimental.pallas import tpu as pltpu

    s = seed_ref[0, 0] * _HASH_A + tile_idx * _HASH_B
    pltpu.prng_seed(s * _HASH_A)
    bits = pltpu.prng_random_bits(shape)
    thresh = int(min(max(-2 ** 31 + rate * 2 ** 32, -2 ** 31), 2 ** 31 - 1))
    return bits >= jnp.int32(thresh)


def _dropout_kernel(seed_ref, x_ref, o_ref, *, rate):
    from jax.experimental import pallas as pl

    keep = _mask_for_tile(seed_ref, pl.program_id(0), x_ref.shape, rate)
    inv = 1.0 / (1.0 - rate)
    x = x_ref[...]
    o_ref[...] = jnp.where(keep, x * jnp.asarray(inv, x.dtype),
                           jnp.zeros_like(x))


def _run(x2d, seed, rate, interpret):
    from jax.experimental import pallas as pl

    rows, cols = x2d.shape
    # keep the tensor's own minor dim as the lane dim — reshaping to a
    # different minor dim would be a physical relayout (a full HBM copy,
    # which is exactly what this kernel exists to avoid)
    block_rows = max(1, min(rows, _BLOCK_ELEMS // cols))
    grid = (rows + block_rows - 1) // block_rows
    kern = functools.partial(_dropout_kernel, rate=rate)
    return pl.pallas_call(
        kern,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
        name="pallas_dropout",
    )(seed, x2d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def dropout_tpu(x, seed, rate, interpret=False):
    """Upscale-in-train dropout via the Pallas kernel.

    x: any shape with total size divisible by 128. seed: int32 array shaped
    (1, 1) (scalar-prefetch style, like the flash kernels).
    """
    return _fwd(x, seed, rate, interpret)[0]


def _fwd(x, seed, rate, interpret):
    x2d = x.reshape(-1, x.shape[-1])     # free: minor dim unchanged
    out = _run(x2d, seed, rate, interpret).reshape(x.shape)
    return out, (seed,)


def _bwd(rate, interpret, res, dy):
    (seed,) = res
    dy2d = dy.reshape(-1, dy.shape[-1])
    dx = _run(dy2d, seed, rate, interpret).reshape(dy.shape)
    return dx, None


dropout_tpu.defvjp(_fwd, _bwd)


def supports(x, rate) -> bool:
    """Kernel applicability: a lane-aligned minor dim (so the 2D view is
    layout-free) and a nontrivial rate."""
    if not (0.0 < rate < 1.0) or not x.shape:
        return False
    return x.shape[-1] % _LANES == 0 and int(np.prod(x.shape)) > 0
