"""Sparse-expert (mixture-of-experts) ops: a top-k router (softmax scores, or
sigmoid scores with a selection bias) and a dropless expert layer in four
pieces.

    router:    X [N, D], W [D, E] (, Bias [E])  ->  top-k weights / indices,
               counts. Scores by `score_func` (softmax over the experts;
               sigmoid of each); with `Bias` the choice is by score + bias and
               the weights are the scores without it (`_moe_router`)
    dispatch:  the N*k assignments sorted by expert, their rows gathered
               into groups that start and end on a row tile
    grouped_matmul (x3) + swiglu: one matmul per projection over the
               stacked expert weights [E, K, F], rows grouped by expert
               (under a share the gate's and the up projection's are one
               op of two weights)
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
permutations where every expert is held: their hand-written grads gather
through the inverse permutation instead of scatter-adding.

The kernels read a stack row-major, and the chip holds one so unless its
last axis is no whole number of 128-lane tiles while its middle axis is:
then the TPU client puts the middle axis minor-most, for the parameter and
its optimizer moments alike. Of the two-matrix experts' stacks (2688 wide,
experts of 1856) that is `up` `[held, 2688, 1856]`; `down` `[held, 1856,
2688]` and every gated-silu stack (last axes 512 to 2048) lie as written.
Such a stack's three products (forward, the rows' gradient, the weight's)
go through `swapaxes(1, 2)`, a bitcast on an array held that way, with the
kernel's other `transpose_rhs` and the weight gradient swapped back: the
same sums on the operand where it lies, in place of a transposing copy of
the stack and of both moments each way, every step (`_held_lane_major`;
the op counts itself on the compile event, `moe_lane_major_stacks`). The
parameter's shape, the op's slots and `ragged_dot`'s path know nothing of
it.

Under a share (`moe_dispatch` with `experts_held`: one chip of an
expert-parallel layer; the router still chooses among all E experts and the
weights are `[experts_held, K, F]`) the layout holds the assignments to the
held experts only, each group padded to whole tiles (none for an expert
nobody chose), from row 0 on. The rows are static and the worst case, N*k +
held*ROW_TILE: every routing fits, so nothing is ever dropped and nothing
has to be checked; what the held groups do not use lies behind them (the
rule notes the count on the program's compile event, `moe_row_buffer_rows`).
`GroupSizes` sums to what is used, and everything that touches a row follows
it. The kernels' grid is as long as the tiles they visit (megablox counts
them from the group sizes). The four row movements (dispatch, combine and
their grads) go over the used rows only, `sum(GroupSizes)` of them, each op
counting itself on the compile event (`moe_share_bounded_moves`). The two
that write the layout are `lax.while_loop`s of `_MOVE_ROWS` rows a step
(`_over_used_rows`): they gather a chunk's rows by token and write them in
place (`_dispatch_share`, which finds each chunk's `Source` in the same
step, and `_combine_share_grad`). The two that read it (`moe_combine` and
`moe_dispatch_grad`, both of which run once a step: the one has a registered
grad, the other is one) add every used row to its token's row in float32, a
token's experts in expert order, the same order every run
(`_tokens_from_rows`): on a TPU one Pallas call, `moe_token_sum`
(`_token_sum_call`: the rows streamed in chunks, a float32 accumulator of
all the tokens resident in VMEM, a row's token and router weight read from
SMEM where it is added, the result written once in its own dtype; 0.018-0.024
us a used row on a v5e where the loop took 0.10-0.12, PERF.md section 6, PR
50); on a CPU backend, and for a shape outside `_token_sum_plan`, a
`lax.while_loop` that scatter-adds a chunk into a float32 `[N, width]`
array (`_token_sum_loop`), which is also what the kernel is tested against.
So the step's time follows the held assignments: at the even load of one
chip in sixteen (4096 tokens, top 10 of 512, 32 held: ~2560 assignments in
4096-4224 used rows of 45056) the four movements of a layer took 1.7 ms
alone on a v5e as four loops where gathers over the static rows took 9.1;
with every assignment on a held expert (40960, the worst case) 12.3 ms
against 9.1 (PERF.md section 6, PR 37). What stands between dispatch and
combine is elementwise and goes over the used rows the same way, in larger
steps (`map_used_rows`, `_ELEMENTWISE_ROWS`: no gather, so a chunk is plain
traffic): the silu product of the hidden rows and its grad (`swiglu` given
`GroupSizes`, `ops/decoder_block.py`), and the sum of the gate's and the up
projection's input gradients, which `grouped_matmul`'s grad adds in place
where one op holds both weights (two ops would leave the sum to
`append_backward`'s `sum` op, over all the rows). Each of the three
counts itself on the compile event, `moe_share_bounded_ops`. The rows behind
the used ones are never written and never read: their buffers are
allocated, not filled, and hold whatever was in memory. Nothing does
arithmetic on them (but for what the last chunk of a loop reaches past the
used rows, whose results no one reads) or on a padding row that reaches a
result, not even times a zero weight (0 x NaN is NaN): a row's `Source` and
an assignment's `Slot` are -1 where there is nothing, and a movement
applies `Source` with a select or drops the row by an index out of range
(`moe_token_sum` adds it to a spare row of its accumulator, behind the
tokens', that nobody reads).

Where every expert is held all N*k assignments have a row and the movements
are static gathers, token-major `[N, k, D]` (`_rows_of_slots`): the faster
layout there (PERF.md section 6, PR 34). The attribute chooses.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.registry import master_as, register_grad, register_op
from . import _kernels

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
# Rows a step of a share's row movement carries (`_over_used_rows`).
_MOVE_ROWS = 512
# Rows a step of an elementwise pass over a share's used rows carries
# (`map_used_rows`): no gather, so a chunk is plain traffic and a larger one
# pays a `while` iteration's cost less often, but reaches further past the
# used rows. On a v5e the three passes of a layer together read 183 / 179 /
# 203 / 260 us at 512 / 1024 / 2048 / 4096 rows over 4224 used rows of
# [45056, 512 and 2048], and 698 / 604 us at 512 / 1024 over 9216 of
# [66560, 896 and 2304] (PERF.md section 6, PR 43).
_ELEMENTWISE_ROWS = 1024
# `moe_token_sum` (a share's token-side sums as one Pallas call): the most
# the tokens' float32 accumulator `[tokens, columns]` may take of a v5e's 128
# MiB of VMEM, which decides how many column blocks a call makes
# (`_token_sum_plan`), and the most its scalar-prefetch operands (`Source`
# and the router weights) may take of the 1 MiB of SMEM. A row costs about
# as much per column block it is visited in as it has vregs (0.015 us at 9
# vregs, 0.019 at 18: the chain load, add, store of a token's row), so one
# block of 2304 columns over 8192 tokens (72 MiB) is a third faster than two
# of 1152 (v5e, the call alone, PERF.md section 6, PR 50).
_TOKEN_SUM_ACC_BYTES = 72 * 2 ** 20
_TOKEN_SUM_SMEM_BYTES = 768 * 2 ** 10
# Token rows of the result a trailing grid step of `moe_token_sum` casts and
# hands to the pipeline, and source rows a step of its row loop is unrolled by.
_TOKEN_SUM_OUT_ROWS = 512
_TOKEN_SUM_UNROLL = 8


def _kept_groups(choice, groups, kept):
    """`choice` [N, E] with every expert outside the token's `kept` best of
    `groups` groups of consecutive experts at -inf: a group's score is the
    sum of its two largest entries (DeepSeek-V3's group-limited routing, the
    public `deepseek_v3` code). Float32; nothing here is differentiated (the
    weights are gathered from the scores by the indices)."""
    n, e = choice.shape
    two_best, _ = lax.top_k(choice.reshape(n, groups, e // groups), 2)
    _, best = lax.top_k(jnp.sum(two_best, axis=-1), kept)      # [N, kept]
    stays = jnp.any(best[:, :, None] == jnp.arange(groups, dtype=best.dtype),
                    axis=1)                                     # [N, groups]
    return jnp.where(jnp.repeat(stays, e // groups, axis=1), choice,
                     -jnp.inf)


@register_op("moe_router", propagate_seqlen=False)
def _moe_router(ctx, X, W, Bias=None):
    """X [N, D], W [D, E]. Logits, the scores of ALL E experts and both
    router losses' inputs in float32 (AMP_F32_OPS; the product at HIGHEST,
    since a TPU's default float32 product rounds its inputs to bf16 and a
    near-tie between experts flips on that). `Probs` is the score: the
    softmax over the experts, or with the attribute `score_func` "sigmoid"
    each expert's own sigmoid (DeepSeek-V3's router). The k experts are the
    largest scores, or with `Bias` [E] (float32, no gradient) the largest of
    `score + Bias`: the bias moves the choice alone, the k weights are the
    chosen experts' scores without it. The weights are the scores as they
    are, or, with `norm_topk_prob`, divided by their sum over all k chosen
    experts (wherever those live; plus the attribute `norm_eps` where it is
    given), and then times `scaling_factor` where that is given. With the
    attributes `n_group` > 1 and `topk_group` the choice is group-limited
    (`_kept_groups`): the k largest of `score + Bias` among the experts of
    the token's best groups. A program that sets none of these lowers to the
    ops it had."""
    k = int(ctx.attr("k"))
    logits = jnp.dot(X.astype(jnp.float32), W.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    if ctx.attr("score_func", "softmax") == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jnp.exp(logits - lse[:, None])
    groups = int(ctx.attr("n_group", 1))
    if groups > 1:
        choice = probs if Bias is None else probs + Bias.astype(jnp.float32)
        _, index = lax.top_k(_kept_groups(
            choice, groups, int(ctx.attr("topk_group", groups))), k)
        weight = jnp.take_along_axis(probs, index, axis=-1)
    elif Bias is None:
        weight, index = lax.top_k(probs, k)
    else:
        _, index = lax.top_k(probs + Bias.astype(jnp.float32), k)
        weight = jnp.take_along_axis(probs, index, axis=-1)
    if ctx.attr("norm_topk_prob", False):
        total = jnp.sum(weight, axis=-1, keepdims=True)
        if ctx.attr("norm_eps") is not None:
            total = total + float(ctx.attr("norm_eps"))
        weight = weight / total
    if ctx.attr("scaling_factor") is not None:
        weight = weight * float(ctx.attr("scaling_factor"))
    experts = jnp.arange(W.shape[1], dtype=index.dtype)
    counts = jnp.sum(index[:, :, None] == experts, axis=(0, 1),
                     dtype=jnp.int32)
    return {"TopKWeight": weight, "TopKIndex": index.astype(jnp.int32),
            "TokensPerExpert": counts, "Probs": probs, "LogSumExp": lse}


@register_grad("moe_router")
def _moe_router_grad(ctx, ins, out_grads):
    """dX and dW from the forward's saved `Probs`, `TopKIndex` and
    `TopKWeight`: no second trace of the rule, so no `top_k`, no scatter and
    no element gather of its own. The generic vjp's transpose of `top_k` /
    `take_along_axis` is a scatter-add of N*k updates into [N, E], which a
    TPU walks one update at a time; a row's k indices are distinct, so the
    same array is a compare and select over [N, k, E] summed over k, one
    fused pass. A gather of N*k elements is walked the same way (0.5-0.7 ms
    a router on a v5e where the rest of the grad takes 0.15-0.45), so the
    chosen scores are gathered again only where the forward's own gather is
    there to merge with (PERF.md section 6, PR 62). The choice (the bias,
    `_kept_groups`) carries no gradient, as under `jax.vjp`. The grad op
    sees the scope's values, so AMP_F32_OPS' casts are made here. The op
    counts itself on the compile event (`moe_router_direct_grads`)."""
    X, W = ins["X"][0], ins["W"][0]
    d_weight = out_grads["TopKWeight"][0]
    d_probs, d_lse = out_grads["Probs"][0], out_grads["LogSumExp"][0]
    if d_weight is None and d_probs is None and d_lse is None:
        return {}
    ctx.tally("moe_router_direct_grads")
    f32 = jnp.float32
    index, probs = ctx.fwd_outs["TopKIndex"][0], ctx.fwd_outs["Probs"][0]
    if d_weight is not None:
        d_raw, scale = d_weight.astype(f32), ctx.attr("scaling_factor")
        if scale is not None:
            d_raw = d_raw * float(scale)
        experts = jnp.arange(probs.shape[1], dtype=index.dtype)
        hit = index[:, :, None] == experts                      # [N, k, E]
        norm = bool(ctx.attr("norm_topk_prob", False))
        eps = float(ctx.attr("norm_eps") or 0.0)
        gathered = bool(ins.get("Bias")) or int(ctx.attr("n_group", 1)) > 1
        if norm and gathered:
            # the forward gathered the chosen scores: the same gather
            # again, which XLA merges with the forward's
            raw = jnp.take_along_axis(probs, index, axis=-1)
            total = jnp.sum(raw, axis=-1, keepdims=True) + eps
            d_raw = (d_raw - jnp.sum(d_raw * (raw / total), axis=-1,
                                     keepdims=True)) / total
        if gathered or not norm:
            chosen = jnp.sum(jnp.where(hit, d_raw[:, :, None], 0.0), axis=1)
        else:
            # they were `top_k`'s own values and there is no gather to
            # merge with: the saved weights are their quotients, and their
            # sum comes out of the pass over `hit` (a variadic reduce: XLA
            # does not merge two) as the scores at the chosen experts
            w_norm = ctx.fwd_outs["TopKWeight"][0]
            if scale is not None:
                w_norm = w_norm / float(scale)
            d_raw = d_raw - jnp.sum(d_raw * w_norm, axis=-1, keepdims=True)
            chosen, is_chosen = lax.reduce(
                (jnp.where(hit, d_raw[:, :, None], 0.0), hit),
                (np.float32(0), np.bool_(False)),
                lambda a, b: (a[0] + b[0], a[1] | b[1]), dimensions=(1,))
            chosen = chosen / (jnp.sum(jnp.where(is_chosen, probs, 0.0),
                                       axis=-1, keepdims=True) + eps)
        d_probs = chosen if d_probs is None else d_probs.astype(f32) + chosen
    x32, w32 = X.astype(f32), W.astype(f32)
    if ctx.attr("score_func", "softmax") == "sigmoid":
        d_logits = 0.0 if d_probs is None \
            else d_probs * probs * (1.0 - probs)
        if d_lse is not None:
            # the forward's own expressions: XLA merges the two products
            logits = jnp.dot(x32, w32, precision=lax.Precision.HIGHEST)
            lse = ctx.fwd_outs["LogSumExp"][0]
            d_logits = d_logits + d_lse.astype(f32)[:, None] \
                * jnp.exp(logits - lse[:, None])
    else:
        d_logits = 0.0 if d_probs is None else probs * (
            d_probs - jnp.sum(d_probs * probs, axis=-1, keepdims=True))
        if d_lse is not None:
            d_logits = d_logits + d_lse.astype(f32)[:, None] * probs
    d_x = jnp.dot(d_logits, w32.T, precision=lax.Precision.HIGHEST)
    d_w = jnp.dot(x32.T, d_logits, precision=lax.Precision.HIGHEST)
    grads = {"X": d_x.astype(X.dtype), "W": d_w.astype(W.dtype)}
    if ins.get("Bias"):
        grads["Bias"] = jnp.zeros_like(ins["Bias"][0])
    return grads


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
        ctx.tally("moe_share_bounded_moves")
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


def _over_used_rows(sizes, rows, step, init, at_most=_MOVE_ROWS):
    """`step(start, chunk, carry)` for every chunk of the rows that the held
    groups of a share use, in ascending order: `chunk` rows from `start` on,
    as many chunks as `sizes` (whole tiles, from row 0 on) reach into. A
    `lax.while_loop`: its trip count is the routing's, as the grouped
    kernels' grid is. `chunk` (`at_most` rows) divides the buffer's `rows`,
    so the last chunk never leaves it; what it holds behind the used rows
    has `Source` -1."""
    chunk = math.gcd(rows, at_most)
    steps = -(-jnp.sum(sizes.astype(jnp.int32)) // chunk)
    return lax.fori_loop(
        0, steps, lambda i, carry: step(i * chunk, chunk, carry), init)


def map_used_rows(fn, sizes, *operands, in_place=0):
    """An elementwise pass over a share's used rows: `fn` of every chunk of
    the operands' rows that `sizes` reach into (`_ELEMENTWISE_ROWS` a
    step), its results (a tuple, one `[chunk, ...]` each) written into
    buffers as long as the layout: the first `in_place` of them over the
    used rows of the operands at their places (of the results' shape and
    dtype, and read by nobody afterwards: XLA then takes the buffer as it
    is), the others into allocations. The rows behind the last chunk are
    not visited and keep what the buffer held."""
    def step(start, chunk, outs):
        values = fn(*(lax.dynamic_slice_in_dim(a, start, chunk)
                      for a in outs[:in_place] + operands[in_place:]))
        return tuple(lax.dynamic_update_slice_in_dim(out, v, start, 0)
                     for out, v in zip(outs, values))

    # elementwise over rows: on the whole operands `fn` has the results'
    # whole shapes
    fresh = jax.eval_shape(fn, *operands)[in_place:]
    return _over_used_rows(
        sizes, operands[0].shape[0], step,
        operands[:in_place] + tuple(lax.empty(v.shape, v.dtype)
                                    for v in fresh),
        at_most=_ELEMENTWISE_ROWS)


def _dispatch_share(X, TopKIndex, TokensPerExpert, tile, first, held):
    """The layout of one chip's share: the router chose among all E experts,
    this chip holds experts `first .. first + held - 1`. Only assignments to
    those are placed, grouped by expert, each group padded to whole row
    tiles (none for an expert nobody chose), from row 0 on, in N*k +
    held*tile rows: the worst case (every assignment on a held expert, a
    partly filled tile a group), so every routing fits. `GroupSizes` [held]
    sums to what is used, and the grouped kernels and the row movements
    visit those rows only. The rows after them are never written by
    anybody, so nothing may do arithmetic on them: `Source` is -1 there and
    in padding (the movements go by it), `Slot` is -1 for an assignment to
    an expert that lives elsewhere."""
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
    starts, packed_starts = ends - sizes, packed_ends - counts
    _, slot = lax.sort_key_val(
        order, jnp.where(expert < held, iota + jnp.take(
            starts - packed_starts, jnp.minimum(expert, held - 1)), -1))
    rows = n * k + held * tile

    def step(start, chunk, carry):
        """What rows start .. start + chunk hold, and their rows of X."""
        x_sorted, source = carry
        row = (start + lax.iota(jnp.int32, chunk))[:, None]
        # a row's group picked out of the `held` by a masked sum (1-D
        # gathers from their tables cost more than moving the rows); a row
        # behind the used ones lies in no group and reads 0: not filled
        in_group = (row >= starts[None, :]) & (row < ends[None, :])

        def of_group(table):
            return jnp.sum(jnp.where(in_group, table[None, :], 0), axis=1)

        rank = row[:, 0] - of_group(starts)
        filled = rank < of_group(counts)
        packed = of_group(packed_starts) + rank
        src = jnp.where(filled,
                        jnp.take(order, jnp.where(filled, packed, 0)), -1)
        x_rows = jnp.take(X, jnp.maximum(src, 0) // k, axis=0)
        return (lax.dynamic_update_slice(
            x_sorted, jnp.where(filled[:, None], x_rows, 0), (start, 0)),
                lax.dynamic_update_slice(source, src, (start,)))

    # the rows behind the used ones are not visited: `lax.empty` is an
    # allocation on a TPU (zeros on the CPU backend) and they keep what it
    # held; their `Source` is the fill's -1
    x_sorted, source = _over_used_rows(
        sizes, rows, step, (lax.empty((rows, X.shape[1]), X.dtype),
                            jnp.full((rows,), -1, jnp.int32)))
    return {"XSorted": x_sorted, "Slot": slot, "Source": source,
            "GroupSizes": sizes}


def _token_sum_loop(moved, source, k, n, sizes, scale=None):
    """A share's token-side movement as a `lax.while_loop`, [n, width] in
    float32: every used row r of `moved` (times `scale` [N*k] at r's
    assignment) added to its token's row, in row order: by expert, then by
    token, the same in every run. A padding row's token is `n`, out of
    range, and is dropped by its index; it is not multiplied by a zero. The
    path on a CPU backend and for a shape outside `_token_sum_plan`, and the
    plain form `moe_token_sum` is held against."""
    rows, width = moved.shape

    def step(start, chunk, acc):
        src = lax.dynamic_slice(source, (start,), (chunk,))
        value = lax.dynamic_slice(moved, (start, 0), (chunk, width)) \
            .astype(jnp.float32)
        if scale is not None:
            value = value * jnp.take(scale, jnp.maximum(src, 0))[:, None]
        return acc.at[jnp.where(src >= 0, src // k, n)].add(value,
                                                            mode="drop")

    return _over_used_rows(sizes, rows, step,
                           jnp.zeros((n, width), jnp.float32))


def _token_sum_plan(n, k, rows, width, dtype):
    """(columns of a block, rows of a chunk, token rows of a written block)
    of `moe_token_sum` for `rows` x `width` layout rows of `dtype` summed
    into `n` tokens of `k` assignments each, or None where the loop stays:
    a width that is not whole 128-lane tiles, chunks or written blocks
    (`gcd` of the rows with `_MOVE_ROWS`, of the tokens with
    `_TOKEN_SUM_OUT_ROWS`) that are not whole sublane tiles of `dtype`
    (fewer than 8 rows of float32, 16 of bf16), an accumulator that does
    not fit `_TOKEN_SUM_ACC_BYTES` at 128 columns, `Source` and the weights
    over `_TOKEN_SUM_SMEM_BYTES`. The columns are the widest whole-lane
    divisor of the width whose accumulator fits: 2048 of 2048 at 4096
    tokens, 2304 of 2304 at 8192. The choice reads the shapes alone."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.bfloat16, jnp.float32) or width % 128:
        return None
    tile = 8 * 4 // dtype.itemsize
    chunk = math.gcd(rows, _MOVE_ROWS)
    written = math.gcd(n, _TOKEN_SUM_OUT_ROWS)
    if chunk % tile or written % tile \
            or 4 * (rows + n * k) > _TOKEN_SUM_SMEM_BYTES:
        return None
    lanes = width // 128
    for blocks in range(1, lanes + 1):
        if lanes % blocks == 0 \
                and n * (width // blocks) * 4 <= _TOKEN_SUM_ACC_BYTES:
            return width // blocks, chunk, written
    return None


def _token_sum_kernel(used_ref, source_ref, *refs, k, n, steps, scaled):
    """One (column block, step) of `moe_token_sum`. The first `steps` steps
    are the layout's chunks: a chunk the held groups reach into is widened
    to float32 and its rows are added, in row order, each to its token's row
    of the accumulator; a padding row's token is `n`, the row behind the
    tokens', which nobody reads. The steps after them write the accumulator
    out, a block of token rows each, in the result's dtype."""
    from jax.experimental import pallas as pl

    if scaled:
        scale_ref, rows_ref, out_ref, acc_ref, wide_ref = refs
    else:
        rows_ref, out_ref, acc_ref, wide_ref = refs
    step = pl.program_id(1)
    chunk, columns = rows_ref.shape
    written = out_ref.shape[0]

    @pl.when(step == 0)
    def _():
        def clear(b, carry):
            acc_ref[pl.ds(pl.multiple_of(b * written, written), written), :] \
                = jnp.zeros((written, columns), jnp.float32)
            return carry
        lax.fori_loop(0, n // written, clear, 0)

    @pl.when(step < used_ref[0])
    def _():
        wide_ref[...] = rows_ref[...].astype(jnp.float32)

        def add(b, carry):
            first = pl.multiple_of(b * _TOKEN_SUM_UNROLL, _TOKEN_SUM_UNROLL)
            for r in range(_TOKEN_SUM_UNROLL):
                src = source_ref[step * chunk + first + r]
                token = jnp.where(src >= 0, lax.div(src, k), n)
                value = wide_ref[pl.ds(first + r, 1), :]
                if scaled:
                    value = value * scale_ref[jnp.maximum(src, 0)]
                acc_ref[pl.ds(token, 1), :] += value
            return carry
        lax.fori_loop(0, chunk // _TOKEN_SUM_UNROLL, add, 0)

    @pl.when(step >= steps)
    def _():
        block = pl.multiple_of((step - steps) * written, written)
        out_ref[...] = acc_ref[pl.ds(block, written), :].astype(out_ref.dtype)


def _token_sum_call(moved, source, k, n, sizes, dtype, plan, scale=None):
    """`moe_token_sum`: `_token_sum_loop`'s sums as one Pallas call, the
    same additions in the same order, [n, width] in `dtype`. Grid (column
    blocks, the layout's chunks and then the result's blocks of token rows),
    the second axis in turn. A column block's float32 accumulator
    `[n + 8, columns]` stays in VMEM from its first step to its last, so no
    float32 `[n, width]` array is in HBM and no cast follows. `Source` and
    the flat router weights are scalar-prefetch operands: a row's token and
    weight are read from SMEM where the row is added, not gathered into
    `[rows]` arrays first. The number of chunks the held groups reach into
    goes in front of them: the steps behind it do nothing, and the rows'
    index map stays on the last used chunk there, so the pipeline fetches
    nothing again. The call asks for the VMEM it needs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, width = moved.shape
    columns, chunk, written = plan
    steps = rows // chunk
    used = -(-jnp.sum(sizes.astype(jnp.int32)) // chunk)
    scalars = (used.reshape(1), source) \
        + (() if scale is None else (scale,))
    item = moved.dtype.itemsize
    vmem = (n + 8) * columns * 4 + chunk * columns * (4 + 2 * item) \
        + 2 * written * columns * jnp.dtype(dtype).itemsize + 2 ** 20
    return pl.pallas_call(
        functools.partial(_token_sum_kernel, k=k, n=n, steps=steps,
                          scaled=scale is not None),
        name="moe_token_sum",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(width // columns, steps + n // written),
            in_specs=[pl.BlockSpec(
                (chunk, columns), lambda j, i, used, *_: (
                    jnp.minimum(i, jnp.maximum(used[0] - 1, 0)), j))],
            out_specs=pl.BlockSpec(
                (written, columns),
                lambda j, i, *_: (jnp.maximum(i - steps, 0), j)),
            scratch_shapes=[pltpu.VMEM((n + 8, columns), jnp.float32),
                            pltpu.VMEM((chunk, columns), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, width), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=_kernels.interpret())(*scalars, moved)


def _tokens_from_rows(moved, source, k, n, sizes, dtype, scale=None):
    """A share's token-side movement, [n, width] in `dtype`: every used row
    r of `moved` (times `scale` [N*k] at r's assignment) added to its
    token's row in float32, in row order, one rounding at the end. One
    Pallas call where the backend takes kernels and `_token_sum_plan` gives
    one, else the loop and a cast."""
    plan = _token_sum_plan(n, k, *moved.shape, moved.dtype)
    if plan is not None and _kernels.backend_takes_kernels():
        return _token_sum_call(moved, source, k, n, sizes, dtype, plan,
                               scale)
    return _token_sum_loop(moved, source, k, n, sizes, scale).astype(dtype)


def _rows_of_slots(rows, slot, n, k):
    """rows [M, D] at the k slots of n tokens, `[n, k, D]`: where every
    expert is held, every slot has a row and all N*k of them are read."""
    return jnp.take(rows, slot, axis=0).reshape(n, k, -1)


@register_grad("moe_dispatch")
def _moe_dispatch_grad(ctx, ins, out_grads):
    X, index = ins["X"][0], ins["TopKIndex"][0]
    g = out_grads["XSorted"][0]
    if g is None:
        return {}
    n, k = index.shape
    if ctx.attr("experts_held") is not None:
        ctx.tally("moe_share_bounded_moves")
        return {"X": _tokens_from_rows(g, ctx.fwd_outs["Source"][0], k, n,
                                       ctx.fwd_outs["GroupSizes"][0],
                                       X.dtype)}
    per_slot = _rows_of_slots(g, ctx.fwd_outs["Slot"][0], n, k)
    return {"X": jnp.sum(per_slot.astype(jnp.float32), axis=1)
            .astype(X.dtype)}


def _kernel():
    """The megablox module where its kernels are the path (a TPU backend,
    or the CPU under the Pallas interpreter), else None."""
    if not _kernels.backend_takes_kernels():
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
    """(row tile, the whole contraction, columns a step): all the columns
    where `_GMM_BLOCK` holds them (every gated-silu cell); where it does not
    (2688 x 1856, 1856 x 2688: the two-matrix experts), whole 128-lane
    tiles, as few column blocks as it takes and as even as they come (640 of
    1856, 896 of 2688), the last one past the edge where they do not
    divide. The tiles are the product's, whichever way its stack is handed
    to the kernel (`_held_lane_major`): `up` `[8, 2688, 1856]` goes in as
    `[8, 1856, 2688]`, the shape `down` has, and takes the tiles it took."""
    most = max(128, _GMM_BLOCK // contraction)
    if columns > most:
        blocks = -(-columns // (most // 128 * 128))
        most = -(-columns // (blocks * 128)) * 128
    return _row_tile(rows), contraction, min(columns, most)


def _held_lane_major(w):
    """Whether the kernels take the stack w [E, K, F] through its transpose
    [E, F, K]: where they are the path, F is no whole number of 128-lane
    tiles and K is one. The TPU client's default layout of such an array
    (and of its Adam moments) puts the 128-multiple axis minor-most
    (`f32[8,2688,1856]{1,2,0}`), the kernels read their operands row-major,
    and XLA bridges the two with a transposing copy of the whole stack each
    way, parameter and both moments (six a layer a step, 0.51 ms each at
    160 MB: PERF.md section 6, PR 57). On an array held so `swapaxes(1, 2)`
    is a bitcast, so the transposed product reads the stack where it lies
    and the weight gradient is written where Adam wants it. The choice
    reads the shape alone."""
    return _kernel() is not None \
        and w.shape[2] % 128 != 0 and w.shape[1] % 128 == 0


def _grouped_dot(x, w, sizes, transpose_w=False):
    """Rows of group e of x [M, K] times w[e] [K, F] (its transpose if
    `transpose_w`, w then being [E, F, K]) -> [M, F]. The kernel is handed
    w as it is, or swapped and with the other `transpose_rhs` where the
    chip holds it swapped (`_held_lane_major`): the same product."""
    sizes = sizes.astype(jnp.int32)
    kernel = _kernel()
    if kernel is None:
        return lax.ragged_dot(x, w.swapaxes(1, 2) if transpose_w else w,
                              sizes)
    columns = w.shape[1] if transpose_w else w.shape[2]
    if _held_lane_major(w):
        w, transpose_w = w.swapaxes(1, 2), not transpose_w
    return kernel.gmm(x, w, sizes, preferred_element_type=x.dtype,
                      tiling=_gmm_tiles(x.shape[0], x.shape[1], columns),
                      transpose_rhs=transpose_w,
                      interpret=_kernels.interpret())


@register_op("grouped_matmul", propagate_seqlen=False)
def _grouped_matmul(ctx, X, W, GroupSizes):
    """X [M, K] with rows grouped by expert, W [E, K, F], GroupSizes [E]
    (sums to M, or under a share to the rows that are used;
    `moe_dispatch`'s padded groups in the expert layer): rows of group e
    times W[e]. Several `W` give as many `Out`s, each the product with one
    of them: the projections that share their rows are one op, so the rows'
    gradient is one variable and `_grouped_matmul_grad` sums its parts. An
    op with a stack that the kernels take through its transpose counts
    itself on the compile event (`moe_lane_major_stacks`), as its grad
    does."""
    stacks = W if isinstance(W, list) else [W]
    if any(_held_lane_major(w) for w in stacks):
        ctx.tally("moe_lane_major_stacks")
    return {"Out": [_grouped_dot(X, w, GroupSizes) for w in stacks]}


def _grouped_dot_grads(x, w, g, sizes):
    """(dX, dW) of `_grouped_dot(x, w, sizes)` under the cotangent g. dW[e]
    = X_e^T g_e, or where the chip holds w swapped (`_held_lane_major`) its
    transpose g_e^T X_e, swapped back: a bitcast there."""
    kernel = _kernel()
    if kernel is None:
        _, vjp = jax.vjp(lambda a, b: lax.ragged_dot(a, b, sizes), x, w)
        return vjp(g)
    d_x = _grouped_dot(g, w, sizes, transpose_w=True)
    swapped = _held_lane_major(w)
    lhs, rhs = (g, x) if swapped else (x, g)
    d_w = kernel.tgmm(lhs.swapaxes(0, 1), rhs, sizes,
                      preferred_element_type=g.dtype,
                      tiling=(_row_tile(x.shape[0]),
                              min(lhs.shape[1], _TGMM_BLOCK[0]),
                              min(rhs.shape[1], _TGMM_BLOCK[1])),
                      num_actual_groups=w.shape[0],
                      interpret=_kernels.interpret())
    return d_x, d_w.swapaxes(1, 2) if swapped else d_w


@register_grad("grouped_matmul")
def _grouped_matmul_grad(ctx, ins, out_grads):
    """dX = rows of dOut times W[e]^T; dW[e] = X_e^T dOut_e. The grad op sees
    the scope's values, so the float32 master weights become bf16 here, as
    AMP_BF16_OPS has them for the forward rule: through `master_as`, which
    hands out the step's shadow of a stack where it carries one and casts
    nothing then. With several `W` dX is the sum of their parts over the rows that `GroupSizes` uses, added in
    place and in dX's dtype, as the `sum` op would add them (the op counts
    itself, `moe_share_bounded_ops`): the rows behind them are not
    visited. A stack the kernels take through its transpose is counted
    here as in the forward op (`moe_lane_major_stacks`)."""
    X, sizes = ins["X"][0], ins["GroupSizes"][0].astype(jnp.int32)
    d_x, d_ws = None, []
    for i, (W, g) in enumerate(zip(ins["W"], out_grads["Out"])):
        if g is None:
            d_ws.append(None)
            continue
        part, d_w = _grouped_dot_grads(X.astype(g.dtype),
                                       master_as(ctx, "W", i, W, g.dtype),
                                       g, sizes)
        part = part.astype(X.dtype)
        if d_x is None:
            d_x = part
        else:
            d_x, = map_used_rows(lambda a, b: (a + b,), sizes, d_x, part,
                                 in_place=1)
        d_ws.append(d_w.astype(W.dtype))
    if d_x is None:
        return {}
    if len(ins["W"]) > 1:
        ctx.tally("moe_share_bounded_ops")
    if any(_held_lane_major(W) for W in ins["W"]):
        ctx.tally("moe_lane_major_stacks")
    return {"X": d_x, "W": d_ws}


@register_op("moe_combine", propagate_seqlen=False)
def _moe_combine(ctx, Y, TopKWeight, Slot, Source, GroupSizes=None):
    """Y [rows, D] in `moe_dispatch`'s layout -> Out [N, D]: each token's k
    expert results times its k router weights, summed in float32. Under a
    share (the attribute `experts_held`; `GroupSizes` is given then) the
    used rows are added to their tokens (`_tokens_from_rows`): an assignment
    to an expert that lives elsewhere has no row and adds nothing."""
    n, k = TopKWeight.shape
    weight = TopKWeight.astype(jnp.float32)
    if ctx.attr("experts_held") is not None:
        ctx.tally("moe_share_bounded_moves")
        return {"Out": _tokens_from_rows(Y, Source, k, n, GroupSizes,
                                         Y.dtype, scale=weight.reshape(-1))}
    per_slot = _rows_of_slots(Y, Slot, n, k)
    out = jnp.sum(per_slot.astype(jnp.float32) * weight[:, :, None], axis=1)
    return {"Out": out.astype(Y.dtype)}


def _combine_share_grad(Y, weight, source, sizes, g):
    """`moe_combine`'s gradients under a share, over the used rows: dY[r] =
    w(r) g[token of r], and `dot(Y[r], g[token of r])` is the weight
    gradient of r's assignment (an assignment without a row keeps 0). A
    padding row writes zeros and its assignment is N*k, dropped by index."""
    n, k = weight.shape
    rows, width = Y.shape
    w_flat = weight.reshape(-1).astype(jnp.float32)

    def step(start, chunk, carry):
        d_y, d_w = carry
        src = lax.dynamic_slice(source, (start,), (chunk,))
        held = jnp.maximum(src, 0)
        # gather in the incoming dtype (bf16 under AMP), widen afterwards
        g_rows = jnp.take(g, held // k, axis=0).astype(jnp.float32)
        y_rows = lax.dynamic_slice(Y, (start, 0), (chunk, width))
        # a padding row's assignment: out of range, and no two alike
        absent = n * k + lax.iota(jnp.int32, chunk)
        d_w = d_w.at[jnp.where(src >= 0, src, absent)].set(
            jnp.sum(y_rows.astype(jnp.float32) * g_rows, axis=-1),
            mode="drop", unique_indices=True)
        d_rows = jnp.where((src >= 0)[:, None],
                           g_rows * jnp.take(w_flat, held)[:, None], 0)
        return lax.dynamic_update_slice(d_y, d_rows.astype(Y.dtype),
                                        (start, 0)), d_w

    d_y, d_w = _over_used_rows(
        sizes, rows, step, (lax.empty((rows, width), Y.dtype),
                            jnp.zeros((n * k,), jnp.float32)))
    return d_y, d_w.reshape(n, k)


@register_grad("moe_combine")
def _moe_combine_grad(ctx, ins, out_grads):
    Y, weight = ins["Y"][0], ins["TopKWeight"][0]
    slot, source = ins["Slot"][0], ins["Source"][0]
    g = out_grads["Out"][0]
    if g is None:
        return {}
    n, k = weight.shape
    if ctx.attr("experts_held") is not None:
        ctx.tally("moe_share_bounded_moves")
        d_y, d_weight = _combine_share_grad(Y, weight, source,
                                            ins["GroupSizes"][0], g)
        return {"Y": d_y, "TopKWeight": d_weight.astype(weight.dtype)}
    per_slot = _rows_of_slots(Y, slot, n, k)
    d_weight = jnp.sum(per_slot.astype(jnp.float32)
                       * jnp.expand_dims(g.astype(jnp.float32), 1), axis=-1)
    held = jnp.maximum(source, 0)
    # a padding row's weight is 0: no slot read its result
    w_row = jnp.where(source >= 0, jnp.take(
        weight.reshape(-1).astype(jnp.float32), held), 0.0)
    # gather in the incoming dtype (bf16 under AMP), widen afterwards
    d_y = jnp.take(g, held // k, axis=0).astype(jnp.float32) \
        * w_row[:, None]
    return {"Y": d_y.astype(Y.dtype),
            "TopKWeight": d_weight.astype(weight.dtype)}
