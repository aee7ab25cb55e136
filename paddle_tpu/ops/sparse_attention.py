"""A learned choice of keys for softmax attention (DeepSeek-Sparse-Attention,
DeepSeek-V3.2-Exp report): an indexer scores every key below a query's
diagonal and the query attends to its `topk` best. Two ops, neither of which
carries a gradient, so that a device trace tells the product from the
selection:

`dsa_index_scores`: Q `[B, Hi, T, Di]` (the index heads' queries), K
`[B, 1, T, Di]` (their one key head), W `[B, T, Hi]` (a weight a head a
query) give

    I[t, s] = scale * sum_j W[t, j] * ReLU(Q[j, t] . K[s])        s <= t

float32 `[B, T, T]`, minus infinity above the diagonal. The products run in
the operands' dtype (bf16 under AMP) with float32 accumulation; the ReLU, the
weights, the sum over heads and the scale in float32. Computed in tiles of
`tile` x `tile` over the causal triangle: `[T, Hi, T]` never exists.

`dsa_select`: Scores `[B, T, T]` give Kept, int8 `[B, T, T]`: row t holds 1
at the `min(t + 1, topk)` keys of largest score among s <= t, of equal scores
the lower index first, and 0 elsewhere. The comparison is on the float32
bits: a score's bits as a signed integer whose order is the floats' (negative
values with their magnitude bits flipped), the k-th largest of a row found by
bisection over the 32 bits (the count of keys at or above a candidate, bit by
bit from the top), then among the keys that equal it the lowest indices by a
second bisection over the index. `fused_attention(kept=...)` reads the result.

On the TPU both are Pallas kernels (`dsa_index_scores`, `dsa_select`): the
scores a tile a grid step, tiles above the diagonal written and not
computed; the selection a strip of rows a grid step, the strip's keys in
VMEM over all bisection steps, strips whose rows keep every key (rows under
`topk`) without a search, and a strip's search over the columns up to its
diagonal alone. On a CPU backend the jnp forms below run (the numerical
contract: a sort, not a bisection), or the same kernels under the Pallas
interpreter when PADDLE_TPU_PALLAS_INTERPRET=1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op
from . import _kernels

INT_MIN = -2 ** 31
_LANES = 128
_SELECT_ROWS = 128          # rows of a selection strip
_SELECT_CHUNK = 512         # columns a pass over a strip handles at a time
_VMEM_BYTES = 48 * 1024 * 1024


def _on_kernels(T, tile=None):
    """Kernel or jnp form? As `pallas_attention._pallas_ok`: the TPU has no
    second path."""
    supported = T % _LANES == 0 and (tile is None or (
        tile % _LANES == 0 and T % tile == 0))
    if _kernels.on_chip():
        if not supported:
            raise ValueError(
                f"the index kernels on the {jax.default_backend()!r} backend "
                f"need T % 128 == 0 and tiles of a multiple of 128 that "
                f"divide T, got T = {T}, tile = {tile}")
        return True
    return _kernels.interpret() and supported


# ---------------------------------------------------------------------------
# jnp forms (CPU path; the numerical contract)
# ---------------------------------------------------------------------------

def _causal(T):
    return jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]


def index_scores_xla(q, k, w, scale, tile):
    """`dsa_index_scores` by its formula, `tile` queries at a time."""
    B, Hi, T, Di = q.shape
    keys = k[:, 0]
    weights = w.astype(jnp.float32)

    def rows(first):
        qb = lax.dynamic_slice_in_dim(q, first, tile, axis=2)
        wb = lax.dynamic_slice_in_dim(weights, first, tile, axis=1)
        s = jnp.einsum("bhqd,bkd->bhqk", qb, keys,
                       preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * wb.transpose(0, 2, 1)[..., None]
        return jnp.sum(s, axis=1) * scale

    tile = min(tile, T)
    out = lax.map(rows, jnp.arange(0, T, tile))         # [T / tile, B, tile, T]
    out = out.transpose(1, 0, 2, 3).reshape(B, T, T)
    return jnp.where(_causal(T)[None], out, -jnp.inf)


def ordered_bits(x):
    """float32 -> int32 whose signed order is the floats' (-0.0 read as 0.0,
    its equal; minus infinity above INT_MIN, which no score maps to)."""
    x = x.astype(jnp.float32)
    bits = lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x), jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def select_xla(scores, topk):
    """`dsa_select` by a sort: the threshold of a row is the
    `min(t + 1, topk)`-th largest of its keys below the diagonal."""
    B, T, _ = scores.shape
    causal = _causal(T)[None]
    key = jnp.where(causal, ordered_bits(scores), INT_MIN)
    need = jnp.minimum(jnp.arange(T) + 1, topk)                 # [T]
    thr = jnp.take_along_axis(
        jnp.sort(key, axis=-1),
        jnp.broadcast_to((T - need)[None, :, None], (B, T, 1)), axis=-1)
    above = key > thr
    equal = (key == thr) & causal
    spare = need[None, :, None] - jnp.sum(above, axis=-1, keepdims=True)
    first = jnp.cumsum(equal, axis=-1) <= spare
    return ((above | (equal & first)) & causal).astype(jnp.int8)


# ---------------------------------------------------------------------------
# pallas kernels
# ---------------------------------------------------------------------------

def _index_scores_kernel(q_ref, k_ref, w_ref, o_ref, *, scale, heads):
    from jax.experimental import pallas as pl

    qi, kj = pl.program_id(1), pl.program_id(2)
    tq, tk = o_ref.shape[1], o_ref.shape[2]

    @pl.when(kj * tk > qi * tq + tq - 1)        # wholly above the diagonal
    def _future():
        o_ref[0] = jnp.full((tq, tk), -jnp.inf, jnp.float32)

    @pl.when(kj * tk <= qi * tq + tq - 1)
    def _live():
        keys = k_ref[0]
        weights = w_ref[0].astype(jnp.float32)                  # [tq, Hi]
        acc = jnp.zeros((tq, tk), jnp.float32)
        for j in range(heads):
            s = lax.dot_general(q_ref[0, j], keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * weights[:, j:j + 1]
        row = qi * tq + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        col = kj * tk + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        o_ref[0] = jnp.where(col <= row, acc * scale, -jnp.inf)


def index_scores_kernel(q, k, w, scale, tile):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hi, T, Di = q.shape
    tile = min(tile, T)
    return pl.pallas_call(
        functools.partial(_index_scores_kernel, scale=scale, heads=Hi),
        grid=(B, T // tile, T // tile),
        in_specs=[
            pl.BlockSpec((1, Hi, tile, Di), lambda b, i, j: (b, 0, i, 0)),
            # a tile above the diagonal stays on the diagonal's keys: no fetch
            pl.BlockSpec((1, tile, Di),
                         lambda b, i, j: (b, jnp.minimum(i, j), 0)),
            pl.BlockSpec((1, tile, Hi), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile, tile), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, T, T), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=_kernels.interpret(),
        name="dsa_index_scores",
    )(q, k[:, 0], w)


def _select_kernel(s_ref, o_ref, key_sc, *, topk, chunk):
    """One strip of rows: its keys into scratch, the threshold by bisection
    over the bits, the ties by bisection over the index, the kept set."""
    from jax.experimental import pallas as pl

    rows, T = key_sc.shape
    row0 = pl.program_id(1) * rows
    live = (row0 + rows + chunk - 1) // chunk       # chunks up to the diagonal
    row = row0 + lax.broadcasted_iota(jnp.int32, (rows, chunk), 0)
    lane = lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)

    def cols(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    def causal(c):
        return c * chunk + lane <= row

    def count(pred):
        """How many keys of each row `pred(keys, chunk index)` holds for,
        float32 [rows, 1] (exact: a row has at most T < 2^24)."""
        def body(c, acc):
            hit = jnp.where(pred(key_sc[:, cols(c)], c), 1.0, 0.0)
            for g in range(chunk // _LANES):
                acc = acc + hit[:, g * _LANES:(g + 1) * _LANES]
            return acc
        acc = lax.fori_loop(0, live, body,
                            jnp.zeros((rows, _LANES), jnp.float32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def write(c, kept):
        o_ref[0, :, cols(c)] = jnp.where(kept, 1, 0).astype(jnp.int8)

    def dead(c, _):
        write(c, jnp.zeros((rows, chunk), jnp.bool_))

    lax.fori_loop(live, T // chunk, dead, None)

    @pl.when(row0 + rows <= topk)           # every row keeps all it sees
    def _all():
        lax.fori_loop(0, live, lambda c, _: write(c, causal(c)), None)

    @pl.when(row0 + rows > topk)
    def _search():
        def keys(c, _):
            key_sc[:, cols(c)] = jnp.where(
                causal(c), ordered_bits(s_ref[0, :, cols(c)]), INT_MIN)

        lax.fori_loop(0, live, keys, None)
        need = jnp.minimum(
            row0 + lax.broadcasted_iota(jnp.int32, (rows, 1), 0) + 1,
            topk).astype(jnp.float32)

        # the largest value with at least `need` keys at or above it, in the
        # order's unsigned form (`^ INT_MIN`), a bit at a time from the top
        def bit_step(i, low):
            cand = low | lax.shift_left(jnp.int32(1), 31 - i)
            enough = count(lambda key, c: key >= (cand ^ INT_MIN)) >= need
            return jnp.where(enough, cand, low)

        thr = lax.fori_loop(0, 32, bit_step,
                            jnp.zeros((rows, 1), jnp.int32)) ^ INT_MIN
        spare = need - count(lambda key, c: key > thr)

        # of the keys that equal it, those below column `edge`: the largest
        # edge with fewer than `spare` of them before it
        def index_step(i, edge):
            cand = edge | lax.shift_left(jnp.int32(1), (T - 1).bit_length() - 1 - i)
            few = count(lambda key, c:
                        (key == thr) & (c * chunk + lane < cand)) < spare
            return jnp.where(few, cand, edge)

        edge = lax.fori_loop(0, (T - 1).bit_length(), index_step,
                             jnp.zeros((rows, 1), jnp.int32))

        def kept(c, _):
            key = key_sc[:, cols(c)]
            write(c, causal(c) & ((key > thr) | (
                (key == thr) & (c * chunk + lane <= edge))))

        lax.fori_loop(0, live, kept, None)


def select_kernel(scores, topk):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, _ = scores.shape
    rows, chunk = min(_SELECT_ROWS, T), min(_SELECT_CHUNK, T)
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=int(topk), chunk=chunk),
        grid=(B, T // rows),
        in_specs=[pl.BlockSpec((1, rows, T), lambda b, r: (b, r, 0))],
        out_specs=pl.BlockSpec((1, rows, T), lambda b, r: (b, r, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, T), jnp.int8),
        scratch_shapes=[pltpu.VMEM((rows, T), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=_kernels.interpret(),
        name="dsa_select",
    )(scores)


# ---------------------------------------------------------------------------
# the ops
# ---------------------------------------------------------------------------

def _scores_infer(ctx, structs):
    B, _, T, _ = structs["Q"][0].shape
    return {"Scores": jax.ShapeDtypeStruct((B, T, T), jnp.float32)}


@register_op("dsa_index_scores", infer=_scores_infer, propagate_seqlen=False)
def _dsa_index_scores(ctx, Q, K, W):
    """Q [B, Hi, T, Di], K [B, 1, T, Di], W [B, T, Hi] -> Scores float32
    [B, T, T] (see the module's docstring). attrs: `scale`, `tile`."""
    if K.dtype != Q.dtype:
        K = K.astype(Q.dtype)
    scale, tile = ctx.attr("scale", 1.0), ctx.attr("tile", 512)
    T = Q.shape[2]
    form = index_scores_kernel if _on_kernels(T, min(tile, T)) \
        else index_scores_xla
    return {"Scores": lax.stop_gradient(form(Q, K, W, scale, tile))}


def _select_infer(ctx, structs):
    return {"Kept": jax.ShapeDtypeStruct(structs["Scores"][0].shape,
                                         jnp.int8)}


@register_op("dsa_select", infer=_select_infer, propagate_seqlen=False)
def _dsa_select(ctx, Scores):
    """Scores float32 [B, T, T] -> Kept int8 [B, T, T] (see the module's
    docstring). attrs: `topk`."""
    topk = int(ctx.attr("topk"))
    form = select_kernel if _on_kernels(Scores.shape[1]) else select_xla
    return {"Kept": form(Scores, topk)}
