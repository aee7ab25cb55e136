"""Sparse-expert (mixture-of-experts) ops: a softmax top-k router and a
dropless expert layer in four pieces.

    router:    X [N, D], W [D, E]  ->  top-k weights / indices, counts
    dispatch:  the N*k assignments sorted by expert, their rows gathered
               into groups that start and end on a row tile
    grouped_matmul (x3) + swiglu: one matmul per projection over the
               stacked expert weights [E, K, F], rows grouped by expert
    combine:   rows back in token order, weighted sum over the k slots

Dropless: every assignment is computed; a group holds whatever the router
sent to that expert (nothing included), so `GroupSizes` is data, not a
capacity. The grouped kernel works in row tiles and visits a tile once for
every group with rows in it, so on packed rows its time follows the routing
(one more visit for every group that starts inside a tile, none for an empty
expert: -12% from even routing to 24 of 64 experts empty, PERF.md section
6). `moe_dispatch` therefore lays the rows out in N*k + E*ROW_TILE rows:
each group padded to whole tiles, at least one, the spare tiles given to the
last group. Every tile is then visited exactly once whatever the router
does, the step's time does not depend on its data, and no row is left
unwritten; a padding row is zero, no slot reads its result and its gradient
is zero. On the TPU the grouped matmul is the Pallas megablox kernel that
jax ships (`jax.experimental.pallas.ops.tpu.megablox`: instructions `%gmm*`
for rows x weights, `%tgmm*` for the weight gradient), chosen over
`jax.lax.ragged_dot` by a chip measurement (PERF.md section 6: XLA:TPU lowers
`ragged_dot` to the same kernel at fixed 512^3 tiles and transposes the
stacked weights in HBM for the input gradient). A Mosaic call in a rule
would run again inside the generic vjp grad op, so `grouped_matmul`
registers its own grad: the forward kernels run once a step. On a CPU
backend `ragged_dot` is the path (the kernel only under the Pallas
interpreter, PADDLE_TPU_PALLAS_INTERPRET=1). Dispatch and combine are
permutations: their hand-written grads gather through the inverse
permutation instead of scatter-adding.

Under a share (`moe_dispatch` with `experts_held`: one chip of an
expert-parallel layer; the router still chooses among all E experts and the
weights are `[experts_held, K, F]`) the layout holds the assignments to the
held experts only, each group padded to whole tiles (none for an expert
nobody chose), from row 0 on. The rows are static and the worst case, N*k +
held*ROW_TILE: every routing fits, so nothing is ever dropped and nothing
has to be checked; what the held groups do not use lies behind them (the
rule notes the count on the program's compile event, `moe_row_buffer_rows`).
`GroupSizes` sums to what is used, and the kernels' grid is as long as the
tiles they visit (megablox counts them from the group sizes), so the work
follows the held assignments and the unused rows are never written: they
hold whatever was in memory. Nothing does arithmetic on them, not even times
a zero weight (0 x NaN is NaN): a row's `Source` and an assignment's `Slot`
are -1 where there is nothing, and both are applied with a select.

Two ways to read a token's k rows back (`_rows_of_slots`): token-major
`[N, k, D]` where every expert is held, slot-major `[k, N, D]` with the
select under a share. Each is the faster one on its side (measured both
ways on the chip, PERF.md section 6, PR 34), so both stay, chosen by the
attribute.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_grad, register_op
from .pallas_attention import _interpret

# Rows a group is padded to in `moe_dispatch`, and the row tile of the
# kernels: equal, so that no tile holds rows of two groups.
ROW_TILE = 128
# Elements of the weight block [contraction, columns] of one `gmm` step: the
# whole contraction, so that the row tiles of a group, which follow one
# another, find their expert's block in VMEM and do not load it again. That
# is what makes 128-row tiles as fast as 256 (v5e, 64 groups of ~512 rows,
# PERF.md section 6). `tgmm` accumulates a [contraction, columns] block in
# float32 over a group's row tiles, _TGMM_BLOCK of it a step.
_GMM_BLOCK = 2048 * 1024
_TGMM_BLOCK = (1024, 1024)


@register_op("moe_router", propagate_seqlen=False)
def _moe_router(ctx, X, W):
    """X [N, D], W [D, E]. Logits, softmax over ALL E experts and both
    router losses' inputs in float32 (AMP_F32_OPS; the product at HIGHEST,
    since a TPU's default float32 product rounds its inputs to bf16 and a
    near-tie between experts flips on that). The k weights are the
    probabilities as they are, or, with the attribute `norm_topk_prob`,
    divided by their sum over all k chosen experts (wherever those live)."""
    k = int(ctx.attr("k"))
    logits = jnp.dot(X.astype(jnp.float32), W.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])
    weight, index = lax.top_k(probs, k)
    if ctx.attr("norm_topk_prob", False):
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    experts = jnp.arange(W.shape[1], dtype=index.dtype)
    counts = jnp.sum(index[:, :, None] == experts, axis=(0, 1),
                     dtype=jnp.int32)
    return {"TopKWeight": weight, "TopKIndex": index.astype(jnp.int32),
            "TokensPerExpert": counts, "Probs": probs, "LogSumExp": lse}


def _padded_groups(counts, rows, tile):
    """Sizes [E] of the padded groups: whole tiles, at least one each, the
    last group taking the tiles left over, `rows` in all."""
    sizes = jnp.maximum(-(-counts // tile), 1) * tile
    return sizes.at[-1].add(rows - jnp.sum(sizes))


@register_op("moe_dispatch", propagate_seqlen=False)
def _moe_dispatch(ctx, X, TopKIndex, TokensPerExpert):
    """X [N, D], TopKIndex [N, k], TokensPerExpert [E] -> XSorted
    [N*k + E*row_tile, D]: each token's row once per expert it goes to,
    grouped by expert, each group padded to whole row tiles (module
    docstring). `Slot` [N*k]: the row that holds assignment a (token a // k).
    `Source` [rows]: the assignment a row holds, -1 for padding.
    `GroupSizes` [E]: the padded groups, which fill the rows. With the
    attribute `experts_held` it lays out a share instead (`_dispatch_share`)."""
    tile = int(ctx.attr("row_tile"))
    n, k = TopKIndex.shape
    if ctx.attr("experts_held") is not None:
        held = int(ctx.attr("experts_held"))
        ctx.note(moe_row_buffer_rows=n * k + held * tile)
        return _dispatch_share(X, TopKIndex, TokensPerExpert, tile,
                               int(ctx.attr("first_expert")), held)
    counts = TokensPerExpert.astype(jnp.int32)
    rows = n * k + counts.shape[0] * tile
    sizes = _padded_groups(counts, rows, tile)
    ends, packed_ends = jnp.cumsum(sizes), jnp.cumsum(counts)
    # sorted position p holds assignment order[p], of expert expert[p]
    iota = lax.iota(jnp.int32, n * k)
    expert, order = lax.sort_key_val(TopKIndex.reshape(-1), iota,
                                     is_stable=True)
    shift = (ends - sizes) - (packed_ends - counts)
    _, slot = lax.sort_key_val(order, iota + jnp.take(shift, expert))
    row = lax.iota(jnp.int32, rows)
    group = jnp.sum(row[:, None] >= ends[None, :-1], axis=1, dtype=jnp.int32)
    rank = row - jnp.take(ends - sizes, group)
    held = rank < jnp.take(counts, group)
    packed = jnp.take(packed_ends - counts, group) + rank
    source = jnp.where(held, jnp.take(order, jnp.where(held, packed, 0)), -1)
    x_rows = jnp.take(X, jnp.maximum(source, 0) // k, axis=0)
    return {"XSorted": jnp.where(held[:, None], x_rows, 0),
            "Slot": slot, "Source": source, "GroupSizes": sizes}


def _dispatch_share(X, TopKIndex, TokensPerExpert, tile, first, held):
    """The layout of one chip's share: the router chose among all E experts,
    this chip holds experts `first .. first + held - 1`. Only assignments to
    those are placed, grouped by expert, each group padded to whole row
    tiles (none for an expert nobody chose), from row 0 on, in N*k +
    held*tile rows: the worst case (every assignment on a held expert, a
    partly filled tile a group), so every routing fits. `GroupSizes` [held]
    sums to what is used, and the grouped kernels visit those tiles only.
    The rows after them are never written by anybody, so nothing may do
    arithmetic on them: `Source` is -1 there and in padding, `Slot` is -1
    for an assignment to an expert that lives elsewhere, and both are
    applied with a select."""
    n, k = TopKIndex.shape
    counts = TokensPerExpert.astype(jnp.int32)[first:first + held]
    sizes = -(-counts // tile) * tile
    ends, packed_ends = jnp.cumsum(sizes), jnp.cumsum(counts)
    local = TopKIndex.reshape(-1) - first
    inside = (local >= 0) & (local < held)
    # sorted position p holds assignment order[p] of held expert expert[p];
    # assignments to experts that live elsewhere sort behind all of those
    iota = lax.iota(jnp.int32, n * k)
    expert, order = lax.sort_key_val(jnp.where(inside, local, held), iota,
                                     is_stable=True)
    shift = (ends - sizes) - (packed_ends - counts)
    _, slot = lax.sort_key_val(
        order, jnp.where(expert < held, iota + jnp.take(
            shift, jnp.minimum(expert, held - 1)), -1))
    row = lax.iota(jnp.int32, n * k + held * tile)
    group = jnp.sum(row[:, None] >= ends[None, :-1], axis=1, dtype=jnp.int32)
    rank = row - jnp.take(ends - sizes, group)
    filled = rank < jnp.take(counts, group)
    packed = jnp.take(packed_ends - counts, group) + rank
    source = jnp.where(filled,
                       jnp.take(order, jnp.where(filled, packed, 0)), -1)
    x_rows = jnp.take(X, jnp.maximum(source, 0) // k, axis=0)
    return {"XSorted": jnp.where(filled[:, None], x_rows, 0),
            "Slot": slot, "Source": source, "GroupSizes": sizes}


def _rows_of_slots(ctx, rows, slot, n, k):
    """rows [M, D] at the k slots of n tokens, and the axis the k slots lie
    on. Every expert held: `[n, k, D]`, 1. Under a share (the attribute
    `experts_held`): `[k, n, D]`, 0, and an assignment to an expert that
    lives elsewhere (slot -1) reads zeros, by a select. Slot-major there
    because a `[n, k, D]` array whose k is no multiple of 8 (ten experts a
    token) is laid out in padded tiles on a TPU, and the reshape into it is a
    copy of every gathered row; `[k, n, D]` is free (-18.4 ms a step at k =
    10). Token-major where every expert is held because it is the faster
    one there: at k = 8 slot-major takes 1.9 ms more of a 66.9 ms step
    (chip runs, PERF.md section 6, PR 34)."""
    if ctx.attr("experts_held") is None:
        return jnp.take(rows, slot, axis=0).reshape(n, k, -1), 1
    slot = slot.reshape(n, k).T.reshape(-1)
    per_slot = jnp.take(rows, jnp.maximum(slot, 0), axis=0)
    return jnp.where((slot >= 0)[:, None], per_slot, 0).reshape(k, n, -1), 0


@register_grad("moe_dispatch")
def _moe_dispatch_grad(ctx, ins, out_grads):
    X, index = ins["X"][0], ins["TopKIndex"][0]
    g = out_grads["XSorted"][0]
    if g is None:
        return {}
    per_slot, axis = _rows_of_slots(ctx, g, ctx.fwd_outs["Slot"][0],
                                    *index.shape)
    return {"X": jnp.sum(per_slot.astype(jnp.float32), axis=axis)
            .astype(X.dtype)}


def _kernel():
    """The megablox module where its kernels are the path (a TPU backend,
    or the CPU under the Pallas interpreter), else None."""
    if jax.default_backend() == "cpu" and not _interpret():
        return None
    import importlib
    # the package re-exports a function under the submodule's name
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _row_tile(rows):
    for tile in (ROW_TILE, 64, 32, 16, 8):
        if rows % tile == 0:
            return tile
    raise ValueError(f"grouped matmul needs a multiple of 8 rows, got {rows}")


def _gmm_tiles(rows, contraction, columns):
    return (_row_tile(rows), contraction,
            min(columns, max(128, _GMM_BLOCK // contraction)))


def _grouped_dot(x, w, sizes, transpose_w=False):
    """Rows of group e of x [M, K] times w[e] [K, F] (its transpose if
    `transpose_w`, w then being [E, F, K]) -> [M, F]."""
    sizes = sizes.astype(jnp.int32)
    kernel = _kernel()
    if kernel is None:
        return lax.ragged_dot(x, w.swapaxes(1, 2) if transpose_w else w,
                              sizes)
    columns = w.shape[1] if transpose_w else w.shape[2]
    return kernel.gmm(x, w, sizes, preferred_element_type=x.dtype,
                      tiling=_gmm_tiles(x.shape[0], x.shape[1], columns),
                      transpose_rhs=transpose_w, interpret=_interpret())


@register_op("grouped_matmul", propagate_seqlen=False)
def _grouped_matmul(ctx, X, W, GroupSizes):
    """X [M, K] with rows grouped by expert, W [E, K, F], GroupSizes [E]
    (sums to M; `moe_dispatch`'s padded groups in the expert layer): rows
    of group e times W[e]."""
    return {"Out": _grouped_dot(X, W, GroupSizes)}


@register_grad("grouped_matmul")
def _grouped_matmul_grad(ctx, ins, out_grads):
    """dX = rows of dOut times W[e]^T; dW[e] = X_e^T dOut_e. The grad op sees
    the scope's values, so the float32 master weights are cast here, as
    AMP_BF16_OPS casts them for the forward rule."""
    X, W, sizes = ins["X"][0], ins["W"][0], ins["GroupSizes"][0]
    g = out_grads["Out"][0]
    if g is None:
        return {}
    sizes = sizes.astype(jnp.int32)
    x, w = X.astype(g.dtype), W.astype(g.dtype)
    kernel = _kernel()
    if kernel is None:
        _, vjp = jax.vjp(lambda a, b: lax.ragged_dot(a, b, sizes), x, w)
        d_x, d_w = vjp(g)
    else:
        d_x = _grouped_dot(g, w, sizes, transpose_w=True)
        d_w = kernel.tgmm(x.swapaxes(0, 1), g, sizes,
                          preferred_element_type=g.dtype,
                          tiling=(_row_tile(x.shape[0]),
                                  min(x.shape[1], _TGMM_BLOCK[0]),
                                  min(g.shape[1], _TGMM_BLOCK[1])),
                          num_actual_groups=W.shape[0],
                          interpret=_interpret())
    return {"X": d_x.astype(X.dtype), "W": d_w.astype(W.dtype)}


@register_op("moe_combine", propagate_seqlen=False)
def _moe_combine(ctx, Y, TopKWeight, Slot, Source):
    """Y [rows, D] in `moe_dispatch`'s layout -> Out [N, D]: each token's k
    expert results times its k router weights, summed in float32. Under a
    share (the attribute `experts_held`) an assignment to an expert that
    lives elsewhere adds nothing."""
    per_slot, axis = _rows_of_slots(ctx, Y, Slot, *TopKWeight.shape)
    weight = jnp.moveaxis(TopKWeight.astype(jnp.float32), 1, axis)
    out = jnp.sum(per_slot.astype(jnp.float32) * weight[:, :, None],
                  axis=axis)
    return {"Out": out.astype(Y.dtype)}


@register_grad("moe_combine")
def _moe_combine_grad(ctx, ins, out_grads):
    Y, weight = ins["Y"][0], ins["TopKWeight"][0]
    slot, source = ins["Slot"][0], ins["Source"][0]
    g = out_grads["Out"][0]
    if g is None:
        return {}
    n, k = weight.shape
    per_slot, axis = _rows_of_slots(ctx, Y, slot, n, k)
    d_weight = jnp.moveaxis(
        jnp.sum(per_slot.astype(jnp.float32)
                * jnp.expand_dims(g.astype(jnp.float32), axis), axis=-1),
        axis, 1)
    held = jnp.maximum(source, 0)
    # a padding row's weight is 0: no slot read its result
    w_row = jnp.where(source >= 0, jnp.take(
        weight.reshape(-1).astype(jnp.float32), held), 0.0)
    # gather in the incoming dtype (bf16 under AMP), widen afterwards
    d_y = jnp.take(g, held // k, axis=0).astype(jnp.float32) \
        * w_row[:, None]
    return {"Y": d_y.astype(Y.dtype),
            "TopKWeight": d_weight.astype(weight.dtype)}
