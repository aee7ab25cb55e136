"""What every Pallas kernel family asks before it calls a kernel, asked in
one place: does this backend take the kernels (`on_chip`, `interpret`,
`backend_takes_kernels`), and the chunk algebra the two chunked scans
(`linear_attention.py`, `state_space.py`) are written in. The families call
these through the module (`_kernels.interpret()`), so a test or a probe that
compiles for a described chip turns every family with one
`monkeypatch.setattr(_kernels, "interpret", ...)`. Plans, block tables, VMEM
limits, kernel bodies and oracles stay with their families.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax


def interpret():
    """The CPU rehearsal switch. Refused on any other backend: a kernel
    quietly interpreted on the chip would pass every check and prove
    nothing about Mosaic."""
    on = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") == "1"
    if on and jax.default_backend() != "cpu":
        raise RuntimeError(
            f"PADDLE_TPU_PALLAS_INTERPRET=1 is a CPU rehearsal switch; "
            f"refused on the {jax.default_backend()!r} backend — unset it")
    return on


def on_chip():
    return jax.default_backend() != "cpu"


def backend_takes_kernels():
    """Whether this backend takes the kernels for a shape a plan gives
    them: always on a TPU; on a CPU backend only under the interpreter's
    rehearsal switch (`interpret`, refused on the chip), since a model
    interpreted at the cell's widths never ends."""
    return on_chip() or interpret()


# ---------------------------------------------------------------------------
# the chunk algebra of the chunked scans' kernels
# ---------------------------------------------------------------------------

_HI = lax.Precision.HIGHEST
_NN = ((1,), (0,))      # a b
_NT = ((1,), (1,))      # a b^T
_TN = ((0,), (0,))      # a^T b


def _dot(a, b, dims, full=False):
    """The float32 product of two float32 tiles. `full`: HIGHEST, the MXU's
    float32 passes. Otherwise the backend's DEFAULT for float32 operands,
    spelled out: on the chip XLA rounds them to bf16 and makes one pass
    into a float32 accumulator, so the kernel does; under the interpreter
    on a CPU they stay float32, as that backend's dots do."""
    if full:
        return lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)
    if on_chip():
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=jnp.float32)


def _rows(x):
    return jnp.sum(x, axis=1, keepdims=True)            # [n, m] -> [n, 1]


def _cols(x):
    return jnp.sum(x, axis=0, keepdims=True)            # [n, m] -> [1, m]


def _running_sum(g, chunk):     # [B, T, Hv], the sum starting at each chunk
    g = g.astype(jnp.float32)
    by_chunk = g.reshape(g.shape[0], -1, chunk, g.shape[2])
    return jnp.cumsum(by_chunk, axis=2).reshape(g.shape)
