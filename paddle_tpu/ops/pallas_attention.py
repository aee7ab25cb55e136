"""Fused (flash) attention: Pallas TPU kernels + ring-attention building block.

The reference's only attention is an unfused softmax(QK^T)V composition
(reference: python/paddle/fluid/nets.py:329 scaled_dot_product_attention).
TPU-native redesign: Pallas kernels work on [blk_q, blk_k] tiles of the
scores in VMEM, so the [T, T] score matrix never materializes in HBM — O(T)
memory instead of O(T^2) in both forward AND backward. The forward is one
algorithm in two kernels, chosen from the shape alone (`_fwd_plan`): where
a row of keys is one K block the softmax of a q-block is whole in its one
grid step and the kernel keeps no state (`flash_fwd_onepass`: no scratch,
no branch, a two-axis grid); where a row has several, K/V blocks stream
through the grid's innermost dimension and an online-softmax state (running
maximum, running sum, output accumulator) is carried over them in VMEM
scratch (`flash_fwd`). In both the row statistics stay in the layout a
reduction of the score tile along lanes leaves them in (one value a
sublane; lane-replicated `[blk_q, 128]` arrays in scratch), and the one
relayout is the `Lse` row. Both give the same bits at the same tiles. The
backward recomputes the attention weights from the saved logsumexp, once a
(q-block, k-block) tile: one kernel gridded over key blocks, queries
innermost, gives dK and dV from its scratch accumulators and dQ from the
same `ds` tile (five products a tile). dQ is written straight out where a
row is one K block, and accumulates in a VMEM-resident float32 row where it
has several: the row's `[T, D]` accumulator and its `(1, T, D)` output block
stay in VMEM over the row's whole grid, and the kernel asks for the scoped
VMEM they and a step's tiles need (`_fused_bwd_vmem`: 32 MiB at every
benchmark cell's row, 84 at 65536 x 128). Only a row whose need is over the
budget (`_bwd_plan`: 96 MiB of a v5e core's 128; a bf16 row of 65536 x 256)
takes the FlashAttention-2 pair instead, one kernel for dQ gridded over query
blocks and one for dK/dV over key blocks, each recomputing the tile (seven);
the pair is also the tests' bitwise oracle for the fused kernel.

Attention-weight dropout runs inside the kernel using the TPU PRNG
(pltpu.prng_seed / prng_random_bits), re-seeded per (batch·head, q-block,
k-block) so the forward and the backward kernels regenerate identical masks
in any iteration order.

The `fused_attention` op writes two outputs on the kernel path: `Out` and
`Lse`, the forward kernel's log-sum-exp of every score row (float32
[B*H, 1, T], the layout the backward kernels read; never reshaped, never
cast, in no AMP list). Its grad op reads both back and calls the backward
kernel alone, so the forward kernel runs once a step. Where the
forward op left no `Lse` in the environment (a program built without the
slot, ring attention under an 'sp' mesh axis, the CPU reference path) the
grad op traces the forward rule again under `jax.vjp`, which is what the
generic grad lowering (core/lowering.py) does for every op without a grad
rule. That generic path is cheap where XLA merges the duplicated forward,
and a debt wherever the rule holds a custom call, which XLA does not merge:
such an op pays a second call a step. `fused_attention` is the only op on a
training path whose rule holds one, and it has its `grad_lower`.

The value heads have a width of their own. Q and K are `[B, H, T, D]`, V is
`[B, H, T, Dv]`: `Out`, `dOut`, `dV` and the output accumulators are `Dv`
wide, Q, K, dQ, dK and their accumulators `D` wide, `Lse` is the same row
either way, in every kernel (one-pass and streaming forward, fused backward,
split pair). Latent attention (MLA) is the case that differs: query/key heads
of 192 (128 without position + 64 rotary) over value heads of 128; a 192-wide
block is the array's whole last axis, which is a legal block. Where `Dv == D`
every kernel is the instructions it was. `_bwd_plan` reads both: the resident
dQ row is query-wide (its 192 lanes held as 256), dO, v and dV value-wide.

The operands have one of two layouts (the op's `layout`). "BHTD", `[B, H, T,
D]`: the kernels see `[B*H, T, D]` and a grid step's blocks are one head's.
"BTHD", `[B, T, H, D]`, token-major: what a projection's `[B, T, H*D]` output
is under a free reshape. The kernels see that array as it is; a head is a
range of lanes, which the block specs' index maps and a dynamic lane slice
pick, a block holds several heads of one row block (`_token_major_heads`: as
many as the scoped VMEM of the call holds, all eight of 64 lanes at seq 256),
and the grid is (batch, head block, tiles). Heads narrower than a vreg's 128
lanes share a lane group: the products contract over the group's lanes with
the neighbours' zeroed, which at D = 64 costs the MXU pass the head-major
product costs, and results are written under the head's mask. `Out`, dQ, dK
and dV leave in the operands' layout, so no transpose and no second copy of
`Out` stands on either side, forward or backward; `Lse` and the dropout
masks' keys (b * H + h, q tile, k tile) are the head-major call's, and so
are the results, bit for bit on the chip. Both layouts run the same five
kernel bodies: a head-major step has one head, its blocks whole (the
instructions it always was), a token-major step loops over its heads
(`_each_head`).

A causal call may carry a `window` W: key j is visible to query i iff
`0 <= i - j < W` (sliding-window attention). The mask gains its lower edge and
so does the liveness predicate, in one pair of functions that all five kernels
share. The windowed calls shorten the grid's inner axis to the band's width in
tiles (`_band_steps`): a q-block's K tiles are counted from the lowest tile of
its band (the forward and dQ), a k-block's Q tiles up to the highest of its
band (dK/dV and the fused backward), the few steps that fall outside the band
compute nothing and their index maps stay on the live neighbour, so no block
is fetched that is not used. They carry names of their own (`swa_flash_fwd`,
`swa_flash_dq`, ...): the device track tells a windowed layer from a full one.
`W >= T` is plain causal and runs the plain kernels; without a window every
kernel's body is the instructions it was.

A causal grid without a window keeps its shape, (T / BQ) x (T / BK) steps a
head, and the steps wholly above the diagonal compute nothing
(`_dead_steps`: 28 of 64 at 8192 tokens in tiles of 1024, 6 of 16 at 4096).
Such a step is not free where an index map moves on it: it has no products
to hide a fetch behind. So the map of every operand that rides the inner
axis stays on the live tile beside the step in grid order, without a window
exactly as under one: K and V on the row's diagonal tile where the k blocks
run innermost (the streaming forward, dQ), and Q, dOut, `Lse` and delta
(`Out`'s block of a token-major call) on the column's first q block where
the q blocks do (the fused backward, dK/dV). A held index changes which
block lies in VMEM during a step that reads none: `Out`, `Lse`, dQ, dK and
dV are the bits they were. The grid is not folded into its live steps,
which would change the order in which the fused backward sums dQ. A call
that is not causal and a causal row of one K block have no such step and
lower to the text they did.

Under a window or a kept set the causal mask is applied where it can change
a score. A live tile that lies wholly under the diagonal and, under a
window, inside the band is *interior* (`_causal_interior`): the four kernels
that stream tiles run it in a body without the mask's compare-and-select,
and the tiles an edge crosses (the diagonal, the band's lower one) in the
body that has it (`_on_live_tile`): 28 of a head's 36 tiles at 8192 tokens
are interior, 15 of 45 under a window of 1024 in tiles of 512, 18 of 30
under 2048 over 4096 (at the tiles of 1024 such windows take since PR 72,
none of 15 and 3 of 9). Leaving out a select whose predicate is false in every
element changes no bit. A kept set is data and masks every tile of its call;
the causal mask stays on that call's edge tiles, so its meaning does not
rest on the set lying under the diagonal. Its int8 tile is fetched for the
steps that compute one and for no other: its index map reads the call's
held maps (`_kept_spec`). A plain causal call keeps the mask
on every live tile, the instructions it was: at its 1024 x 1024 tiles the
pass hides behind the products and a second body is a cost
(`_interior_apart`); so does every call whose row is one K block.

An edge tile is half masked, and where its edge runs from corner to corner
it is not computed whole. *Aligned* (`_strip_side`): square tiles of 1024,
so the diagonal tile's edge is its own diagonal, and under a window one of
whole tiles, which then takes such tiles (`_blk`), so the band's lower-edge
tile (the one that starts `window` keys under its queries) is the mirror
image. The four kernels that stream tiles
run such a tile in a `pl.when` body of its own, strip by strip
(`_edge_strips`): the forward and dQ by rows (a strip of rows against the
keys it sees: its own statistics, one product a contraction), dK/dV and the
fused backward by keys (a strip of keys against the rows that see it; dQ
from the key strips' `ds`, a row strip's parts side by side in ascending
key order, `_row_shares`), by static slices of the blocks: 10 of the 16
sub-blocks of a tile in fourths. Only the sub-block the edge crosses keeps
a select. Every term a strip leaves out of a row's sum of weights or of a
product's contraction is an exact zero at one end of the sum, and the MXU
adds a contraction's passes in order: `Out`, `Lse`, dQ, dK and dV are the
bits they were (tests/test_flash_grad_tpu.py holds it on the chip at the
cells' shapes). Which strips, and how wide, follows from the shapes alone:
strips of a fourth of 1024 pay 5-12% of a call's forward + backward, strips
of 128 rows cost more than they skip (`_strip_side` has the readings). A
call that is not aligned (tiles that are not square or under 1024, a window
off the tiles), a token-major step of several heads and a row of one K
block keep the bodies they had, the instructions they were.

On a CPU backend the same kernels run under the Pallas interpreter when
PADDLE_TPU_PALLAS_INTERPRET=1 (used by the CPU test suite); otherwise a
pure-jnp reference path takes over there. On the TPU there is no second
path: a shape the kernels do not support raises, and so does the
interpreter switch.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.registry import (amp_cast, call_rule, get_op_def, register_grad,
                             register_op)
from . import _kernels

NEG_INF = -1e30


# sweep override: (BQ, BK) or None -> tuned default (tools/flash_probe.py)
_BLOCK_OVERRIDE = None


# Per-(seq, causal) tuned tiles, round-5 chained sweeps on v5e at D=64
# (tools/flash_block_sweep.py, docs/PERF.md): wide streamed-K blocks win
# every non-causal shape measured — (512, 2048) is +10% over 1024^2 at
# 2048/4096 and +13% at 8192 — while causal keeps 1024^2 at >=4096
# (ties at 8192, loses at 4096) and takes (256, 2048) at 2048 (+27%:
# the whole K/V row sits in one block, so the mask applies in-register
# instead of paying per-block grid iterations). Non-causal T >= 2048
# generalizes the measured pattern; other shapes fall back to the
# biggest power-of-two tile <= 1024 dividing T.
_BLOCK_TABLE = {
    (2048, True): (256, 2048),
}


def _table_blk(T, causal):
    tbl = _BLOCK_TABLE.get((int(T), bool(causal)))
    if tbl is not None:
        return tbl
    if not causal and T >= 2048 and T % 2048 == 0:
        return (512, 2048)
    return None


def _blk(T, causal=False, window=None):
    """Block sizes (BQ, BK) for sequence length T. Tuned by the chained
    sweeps on v5e (tools/flash_block_sweep.py, docs/PERF.md): the
    per-(seq, causal) table above where measured, else the biggest
    power-of-two tile <= 1024 dividing T (the round-4 reproducible
    winner; bigger streamed BK means fewer sequential grid steps to
    pipeline). Since the kernels stream K/V (resp. Q) through the grid's
    innermost dimension, VMEM per program is O(blk_q * blk_k + blk * D)
    regardless of T — no sequence-length cap (validated to seq 32768).

    Under a `window` shorter than T the tiles are square, 512 under a window
    of 512 or more and half a shorter window (128 at least): a band of W keys a row meets about
    W + b keys of b-wide tiles, so 1024 x 1024 tiles at W = 1024 compute
    twice the visible pairs and 512 x 512 one and a half times. Measured at
    two shapes, bf16, forward + backward a layer: [32, 8192, 128] with W =
    1024 (split backward; chip run, PR 40): 8.30 ms at 1024^2, 7.04 at 512^2,
    11.31 at 256^2 (8.4-9.8 at the four mixed shapes); [32, 4096, 128] with
    W = 2048 (fused backward; chip run, PR 49, where the rule was "half the
    window" and gave 1024^2: 9 of the causal 10 tiles): 3.84 ms at 1024^2,
    3.67 at 512^2 (30 of 36), 6.29 at 256^2, 3.95-5.00 at the four mixed
    shapes (without a window there 4.31 at 1024^2, 4.35 at 512^2: the smaller
    tile costs a hundredth, the band's edges a twentieth). Both shapes'
    result was 512 while every tile ran whole. Since PR 72 an aligned edge
    tile of 1024 runs in strips (`_strip_side`), tiles of 1024 in strips
    cover no more than tiles of 512 whole in a third of the grid steps, and
    a window that is whole tiles of 1024 takes them: forward + fused
    backward a call, the kernels alone chained on the host's clock (chip
    runs, PR 72, `tools/interior_mask_probe.py`), tiles of 512 whole ->
    tiles of 1024 in strips: W = 1024 over 8192 tokens 6.185 -> 5.541 ms (a
    head's 45 tiles -> 15, every one an edge tile), W = 2048 over 4096 3.658
    -> 3.328 (30 -> 9, six of them edge tiles). A window of 512 over 4096
    tokens (a differential layer's map: 20 heads, q and k at 64, v at 128;
    chip run, PR 73, `tools/window_tile_probe.py --value-dim 128`), forward +
    fused backward a call: 1.679 ms at the rule's 256^2 (half the window),
    **1.248 at 512^2**, 1.901 at 1024^2, 1.444-1.639 at the four mixed shapes
    (without a window there 2.326 at 1024^2): tiles of 256 cost more in grid
    steps than they save in masked pairs, as they did at both longer windows,
    so since PR 73 a window of 512 or more takes 512 and only a shorter one
    half the window (128 at least), which no run has tried. The rule does not
    consult the sweep table above, whose entries were measured without a
    window."""
    if _BLOCK_OVERRIDE is not None:
        bq, bk = _BLOCK_OVERRIDE
        if T % bq == 0 and T % bk == 0:
            return bq, bk
    if window is not None and window < T:
        if window % _STRIP_TILE == 0 and T % _STRIP_TILE == 0:
            return _STRIP_TILE, _STRIP_TILE
        limit = 512 if window >= 512 else max(window // 2, 128)
        for b in (512, 256, 128):
            if T % b == 0 and b <= limit:
                return b, b
    tbl = _table_blk(T, causal)
    if tbl is not None and T % tbl[0] == 0 and T % tbl[1] == 0:
        return tbl
    for b in (1024, 512, 256, 128):
        if T % b == 0:
            return b, b
    raise ValueError(f"flash attention needs T % 128 == 0, got {T}")


# One vreg of lanes: the last axis of an array in VMEM is held in whole
# multiples of it (a 192-wide float32 row takes the room of a 256-wide one).
_LANES = 128

# The fused backward's scoped VMEM. PR 31 sent a row to the fused kernel only
# while its float32 dQ accumulator was within 2 MiB, the one shape that PR
# had (OLMoE's 4096 x 128); that was the constant's limit, not the chip's (a
# v5e core has 128 MiB of VMEM). The kernel asks for what its row needs
# (`_fused_bwd_vmem`), never less than the 32 MiB the executor gives the
# step's other ops and it asked before, and takes every row whose need is
# within the budget; the need counts `_SCORE_TILES` float32 score-sized
# temporaries a grid step (s, p, dp, ds; more than any shape compiled for a
# described v5e wanted: the least limits that compiled left 2 to 3).
# Measured (TPU v5 lite, libtpu 0.0.34, bf16, causal, the backward alone, ms
# a call split -> fused at the limit asked; dQ, dK, dV bitwise the pair's in
# every row; chip run, PR 41):
#   [16, 4096, 128]  (OLMoE, Ouro)        1.899 -> 1.249 at 32 MiB
#   [32, 4096, 192] over 128 (Kanana-2)   5.542 -> 3.999 at 32
#   [32, 8192, 128]  (Mellum2, full)     14.189 -> 9.299 at 32
#   the same under window 1024, 512^2     4.602 -> 3.311 at 32
#   [16, 4096, 256]  (Qwen3-Next)         3.483 -> 2.385 at 32
#   [4, 16384, 128]                       6.373 -> 4.147 at 36
#   [1, 32768, 128]                       6.143 -> 3.973 at 52
#   [1, 65536, 128]                      24.157 -> 15.596 at 84
# The limit asked is not free: the first three read 1.249, 4.000, 9.298 at
# 24 MiB and 1.314, 4.088, 9.579 at 96, so a row asks for its need and not
# for the budget. Over the budget (a bf16 row of 65536 x 256: 152 MiB) a row
# takes the split pair, which keeps nothing of a row.
_SCORE_TILES = 4
_SCOPED_VMEM_FLOOR_BYTES = 32 * 1024 * 1024
_VMEM_BUDGET_BYTES = 96 * 1024 * 1024


def _fused_bwd_vmem(T, D, Dv, BQ, BK, itemsize):
    """Bytes of scoped VMEM the fused backward asks for at a row of several
    K blocks: the row's dQ (the float32 `[T, D]` accumulator and the
    `(1, T, D)` output block in the input's dtype, double-buffered, both
    held over the row's whole grid), a step's blocks (q, dO; k, v, dK, dV;
    double-buffered), the float32 dK and dV accumulators and the score
    temporaries."""
    d, dv = (-(-x // _LANES) * _LANES for x in (D, Dv))
    row = T * d * (4 + 2 * itemsize)
    blocks = 2 * itemsize * (BQ + 2 * BK) * (d + dv)
    accumulators = 4 * BK * (d + dv)
    scores = _SCORE_TILES * 4 * BQ * BK
    return max(row + blocks + accumulators + scores,
               _SCOPED_VMEM_FLOOR_BYTES)


def _bwd_plan(T, D, Dv, BQ, BK, itemsize):
    """"fused": one kernel gives dQ, dK and dV from one pass over the score
    tiles. "split": dQ and dK/dV each recompute them, where the row's dQ and
    a step's tiles are more VMEM than the budget above. With one K block a
    row (every attention block of both transformer cells) a q-block's dQ is
    complete in its one grid step and nothing is kept. The choice reads the
    input's shape and item size and the tile alone."""
    if T == BK or _fused_bwd_vmem(T, D, Dv, BQ, BK,
                                  itemsize) <= _VMEM_BUDGET_BYTES:
        return "fused"
    return "split"


def _fwd_plan(T, BK):
    """"onepass": a row is one K block, so a q-block's softmax is complete
    in its one grid step and the forward kernel keeps no state (every
    attention block of both transformer cells). "stream": a row has
    several, and the online-softmax state is carried over them in scratch
    (OLMoE, Ouro, the long-context shapes, serving's prefill). One
    algorithm whose bookkeeping is needed or not by what the input is;
    the choice reads the shape alone."""
    return "onepass" if T == BK else "stream"


# ---------------------------------------------------------------------------
# reference jnp implementation (CPU path; the numerical contract)
# ---------------------------------------------------------------------------

def _attention_reference(q, k, v, causal, sm_scale, dropout_rate=0.0,
                         seed=None, window=None, kept=None):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        row = jnp.arange(Tq)[:, None]
        col = jnp.arange(Tk)[None, :]
        s = jnp.where(col > row, NEG_INF, s)
        if window is not None:
            s = jnp.where(row - col >= window, NEG_INF, s)
    if kept is not None:            # [B, T, T]: one set for all heads
        s = jnp.where(kept[:, None] != 0, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate:
        key = jax.random.key(seed if seed is not None else 0)
        keep = jax.random.bernoulli(key, 1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# pallas kernels
# ---------------------------------------------------------------------------

# multiplicative-hash constants (Knuth), expressed as python ints that fit
# int32 so Mosaic folds them; applied in two rounds so adjacent tile indices
# land on well-separated PRNG streams.
_HASH_A = int(np.int32(np.uint32(2654435761)))
_HASH_B = 40503


def _causal_live(qi, kj, blk_q, blk_k, window=None):
    """Whether the (qi, kj) block intersects the causal lower triangle and,
    under a `window` W, the band `0 <= row - col < W` inside it: the block's
    last key has to reach the lowest key its first query sees. A tile that
    is not live is not computed, so coverage is the triangle's tiles without
    a window and the band's with one (at T = 8192, W = 1024 and tiles of
    512: 45 of the triangle's 136). Shared by all the kernels — block coverage and
    dropout-mask seeding are keyed to the same (qi, kj) indices, so the
    fwd/dQ/dKV predicates must be structurally identical. `qi` or `kj` may
    lie outside the array on a windowed grid's spare steps: those are not
    live by the same two inequalities."""
    live = kj * blk_k <= qi * blk_q + blk_q - 1
    if window is not None:
        live = live & (kj * blk_k + blk_k - 1 > qi * blk_q - window)
    return live


def _causal_interior(qi, kj, blk_q, blk_k, window=None):
    """Whether the mask can change no score of the (qi, kj) block: its last
    key is not above its first query and, under a `window` W, its farthest
    pair (last query, first key) is inside the band. `col > row` and
    `row - col >= W` are then false in every element, and the selects of
    `_apply_causal_mask` would hand back the bits they were given. An
    interior tile is live. On Python ints (`interior_tiles`) and on traced
    int32 alike; plain `False`, not traced, where no tile of these sizes fits
    inside the window. Shared by the four kernels that stream tiles, as
    `_causal_live` is."""
    if window is not None and blk_q + blk_k - 2 >= window:
        return False
    inside = kj * blk_k + blk_k - 1 <= qi * blk_q
    if window is not None:
        inside = inside & (qi * blk_q + blk_q - 1 - kj * blk_k < window)
    return inside


def _interior_apart(window, kept):
    """Whether a causal call's interior tiles run in a body of their own,
    without the causal mask: under a `window` or a `kept` set (its ref or
    its array). There other vector work stands beside the mask's and the
    pass shows: 1.9% of a `dsa_` layer's kernels at 8192 x 128, 4.5-4.8% of
    a windowed one's at tiles of 512. A plain causal call at 1024 x 1024
    tiles hides the whole pass behind its products, the mask on no tile at
    all is no faster, and a second body costs it 0.3-0.5% (chip runs, PR 55,
    `tools/interior_mask_probe.py`): it keeps its one body, the instructions
    it was."""
    return window is not None or kept is not None


_ALL = slice(None)


class _Piece(NamedTuple):
    """What a kernel computes of a (qi, kj) tile at once: `rows` of its
    queries and `keys` of its keys, static slices of the blocks, and `mask`,
    what the causal mask does to that many scores (None: nothing). A whole
    tile is one piece of every row and key (`_whole`), the instructions it
    was; an aligned edge tile is several (`_edge_strips`)."""
    rows: slice
    keys: slice
    mask: object

    @property
    def whole(self):
        return self.rows == _ALL


def _sub(idx, part):
    """The index `idx` of a head's rows in a block or in scratch (`_Head`'s
    fields), narrowed to `part` of them; to every one, `idx` as it was."""
    if part == _ALL:
        return idx
    if idx is ...:
        return part
    return (idx, part) if isinstance(idx, int) else idx + (part,)


def _whole(masked, qi, kj, blk_q, blk_k, window=None):
    """The (qi, kj) tile as one piece, under `_apply_causal_mask` where it
    is `masked`."""
    def mask(s):
        return _apply_causal_mask(s, qi, kj, blk_q, blk_k, window)
    return [_Piece(_ALL, _ALL, mask if masked else None)]


# The least tile whose edge tiles run in strips (`_strip_side`).
_STRIP_TILE = 1024


def _strip_side(blk_q, blk_k, window=None):
    """Rows (and keys) of a strip of an *aligned* edge tile, or None where
    an edge tile of these shapes runs whole. Aligned: square tiles, so that
    the diagonal crosses a tile from corner to corner, and under a `window`
    one that is whole tiles long, so that the band's lower edge does too.
    A strip is a fourth of a tile of 1024 or more: 256 rows, whole vregs of
    lanes, which every dtype's sublane packing divides. From the shapes
    alone.
    Measured (TPU v5 lite, bf16, the kernels alone, forward + fused
    backward a call chained on the host's clock, `Out`, `Lse`, dQ, dK, dV
    bitwise the whole tile's in every row; `tools/interior_mask_probe.py`,
    chip runs, PR 72), ms a call, every edge tile whole -> in fourths / in
    halves. Tiles of 1024: [32, 8192, 128] 14.838 -> 14.035 / 14.047, under
    a kept set 15.272 -> 14.393 / 14.405; [32, 4096, 192] over 128 6.362 ->
    5.627 / 5.781; [16, 4096, 128] 2.108 -> 1.893 / 1.898; [16, 4096, 256]
    3.859 -> 3.378 / 3.521; [32, 4096, 64] 4.332 -> 3.931 / 3.947;
    [32, 8192, 128] under W = 1024 7.050 -> 5.541 / 5.463; [32, 4096, 128]
    under W = 2048 3.926 -> 3.328 / 3.287. Tiles of 512 (the windowed calls
    before PR 72): W = 1024 6.185 -> 6.479 / 6.134, W = 2048 3.845 -> 3.903
    / 3.654 (3.658 -> 3.664 in halves in a second call): strips of 128 rows
    cost more than the sub-blocks they skip, and a tile of 512 in halves
    gains nothing. So a tile under 1024 runs whole, and an aligned window
    takes tiles of 1024 (`_blk`)."""
    if blk_q != blk_k or blk_q < _STRIP_TILE \
            or (window is not None and window % blk_k):
        return None
    return blk_q // 4


def _on_edge(qi, kj, blk_q, blk_k, window=None):
    """(Whether the (qi, kj) tile of an aligned call is its row's diagonal
    tile, whether it is the band's lower-edge tile: the one that starts
    `window` keys under its queries.) Every other live tile of such a call
    is interior. On Python ints (`edge_strips`) and on traced int32."""
    diagonal = qi * blk_q == kj * blk_k
    if window is None:
        return diagonal, False
    return diagonal, kj * blk_k == qi * blk_q - window


def _edge_strips(lower, by, blk, side):
    """The live part of an aligned edge tile as `blk // side` pieces. Local
    to the tile, key c is visible to row r iff c <= r on the diagonal tile
    and iff c > r on the band's `lower` one, so of the (blk / side)^2
    sub-blocks of `side` x `side` those on one side of the local diagonal
    are live whole, those on the other dead whole (10 and 6 of 16 in
    fourths, 3 and 1 of 4 in halves), and the ones on it are crossed.
    `by` "rows": row strip i and the keys its rows see, `[0, (i + 1) side)`
    or `[i side, blk)`: rows are independent, so a forward or dQ kernel
    runs each with its own statistics and one product a contraction. `by`
    "keys": key strip j and the rows that see it, `[j side, blk)` or `[0,
    (j + 1) side)`: what a kernel that accumulates dK and dV wants. Each
    piece's mask is one select on its one crossed sub-block, under a
    predicate of local indices. Every term a piece leaves out of a sum of
    the whole tile (a row's sum of weights, a product's contraction) is an
    exact zero at one end of that sum."""
    iota = functools.partial(lax.broadcasted_iota, jnp.int32, (side, side))

    def piece(i):
        own = slice(i * side, (i + 1) * side)
        seen = slice(own.start, blk) if lower == (by == "rows") \
            else slice(0, own.stop)
        axis = 1 if by == "rows" else 0
        at = own.start - seen.start     # the crossed sub-block, in `seen`

        def mask(s):
            dead = iota(1) <= iota(0) if lower else iota(1) > iota(0)
            cut = jnp.where(dead, NEG_INF,
                            lax.slice_in_dim(s, at, at + side, axis=axis))
            parts = [lax.slice_in_dim(s, 0, at, axis=axis), cut,
                     lax.slice_in_dim(s, at + side, s.shape[axis], axis=axis)]
            parts = [x for x in parts if x.shape[axis]]
            return cut if len(parts) == 1 else jnp.concatenate(parts, axis)

        return _Piece(own, seen, mask) if by == "rows" \
            else _Piece(seen, own, mask)

    return [piece(i) for i in range(blk // side)]


def _row_shares(pieces, shares):
    """(rows, their `ds`, its keys) for the dQ of a tile whose `pieces` by
    keys left the `shares` of `ds`: a whole tile's one share as it is; of
    an edge tile in strips, for every row strip the parts of the key strips
    its rows see, side by side in ascending key order."""
    if pieces[0].whole:
        return [(_ALL, shares[0], _ALL)]
    gathered = []
    for own in (pc.keys for pc in pieces):      # a square tile's row strips
        seen = [(pc, ds) for pc, ds in zip(pieces, shares)
                if pc.rows.start <= own.start and own.stop <= pc.rows.stop]
        parts = [lax.slice_in_dim(ds, own.start - pc.rows.start,
                                  own.stop - pc.rows.start, axis=0)
                 for pc, ds in seen]
        gathered.append((own, parts[0] if len(parts) == 1
                         else jnp.concatenate(parts, axis=1),
                         slice(seen[0][0].keys.start, seen[-1][0].keys.stop)))
    return gathered


def _on_live_tile(update, causal, qi, kj, blk_q, blk_k, window, apart,
                  strips=None):
    """`update(pieces)` if the (qi, kj) tile is live. Where interior tiles
    run `apart` (`_interior_apart`, and a row of several K blocks): in a
    body without the causal mask where the tile is interior, in the masked
    body where an edge (the diagonal, the band's lower one) crosses it. Two
    `pl.when` bodies, each straight-line, and not a conditional on the
    score tile, which cuts a body's products from its vector work (a fourth
    slower than the mask on every tile). Where the kernel takes `strips`
    ("rows" or "keys", `_edge_strips`) and the call is aligned
    (`_strip_side`, which holds what strips read on the chip), an edge tile
    runs in a body of its own, strip by strip over its live extent, and
    every other live tile is interior: the masked body is left to the plain
    causal call, which runs its interior tiles in it as it did (a third
    body costs it nothing it does not get back: [16, 4096, 128] 2.108 ->
    1.893 ms forward + backward, chip run, PR 72). A window of one tile has
    no third kind of tile and no third body. Elsewhere, and where the
    window is narrower than a tile, one masked body; a call that is not
    causal has no mask and no condition."""
    from jax.experimental import pallas as pl

    whole = functools.partial(_whole, qi=qi, kj=kj, blk_q=blk_q, blk_k=blk_k,
                              window=window)
    if not causal:
        return update(whole(False))
    live = _causal_live(qi, kj, blk_q, blk_k, window)
    side = strips and _strip_side(blk_q, blk_k, window)
    if side:
        diagonal, lower = _on_edge(qi, kj, blk_q, blk_k, window)
        pl.when(diagonal)(lambda: update(
            _edge_strips(False, strips, blk_q, side)))
        if window is not None:
            pl.when(lower)(lambda: update(
                _edge_strips(True, strips, blk_q, side)))
            diagonal = diagonal | lower
        if window is None or window > blk_k:    # else no third kind of tile
            pl.when(live & jnp.logical_not(diagonal))(
                lambda: update(whole(not apart)))
        return
    interior = apart and _causal_interior(qi, kj, blk_q, blk_k, window)
    if interior is False:
        return pl.when(live)(lambda: update(whole(True)))
    pl.when(interior)(lambda: update(whole(False)))
    pl.when(live & jnp.logical_not(interior))(lambda: update(whole(True)))


def _apply_causal_mask(s, qi, kj, blk_q, blk_k, window=None):
    """Mask strictly-above-diagonal entries of one score tile, and under a
    `window` W those W or more below it."""
    row = qi * blk_q + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    col = kj * blk_k + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    s = jnp.where(col > row, NEG_INF, s)
    if window is not None:
        s = jnp.where(row - col >= window, NEG_INF, s)
    return s


def _apply_kept(s, kept_ref, piece):
    """Mask the scores of one `piece` of a tile by the kept set's tile of
    the same rows and keys
    (int8 `[1, blk_q, blk_k]`, one for all heads of its batch row): a key
    whose entry is 0 is not seen. Widened to int32 first: a v5e's vector
    unit compares no bytes. A tile of ones leaves `s` the bits it had."""
    if kept_ref is None:
        return s
    kept = kept_ref[0] if piece.whole else kept_ref[0, piece.rows, piece.keys]
    return jnp.where(kept.astype(jnp.int32) != 0, s, NEG_INF)


def _masked(s, piece, kept_ref):
    """The scores `s` of one `piece` of a tile under its causal mask, where
    it has one, and under the kept set's."""
    if piece.mask is not None:
        s = piece.mask(s)
    return _apply_kept(s, kept_ref, piece)


# -- the band of a window over tiles ----------------------------------------
# A q-block's band runs from K tile `_first_k` to its diagonal tile
# `_last_k`; a k-block's from Q tile `_first_q` (its diagonal) to `_last_q`.
# The same arithmetic on Python ints (the grid's width) and on traced int32
# (program ids in a kernel, grid indices in an index map).

def _int_ops(x):
    """(max, min, floor division of non-negatives) for `x`'s kind."""
    if isinstance(x, int):
        return max, min, lambda a, b: a // b
    return jnp.maximum, jnp.minimum, lambda a, b: lax.div(a, jnp.int32(b))


def _first_k(qi, blk_q, blk_k, window):
    mx, _, div = _int_ops(qi)
    return div(mx(qi * blk_q - window + 1, 0), blk_k)


def _last_k(qi, blk_q, blk_k):
    return _int_ops(qi)[2](qi * blk_q + blk_q - 1, blk_k)


def _first_q(kj, blk_q, blk_k):
    return _int_ops(kj)[2](kj * blk_k, blk_q)


def _last_q(kj, blk_q, blk_k, window, nq):
    _, mn, div = _int_ops(kj)
    return mn(div(kj * blk_k + blk_k + window - 2, blk_q), nq - 1)


def _band_steps(T, blk_q, blk_k, window):
    """(K tiles the widest band of a q-block spans, Q tiles the widest band
    of a k-block spans): the inner axes of the windowed grids."""
    nq, nk = T // blk_q, T // blk_k
    nkw = max(_last_k(qi, blk_q, blk_k) - _first_k(qi, blk_q, blk_k, window)
              for qi in range(nq)) + 1
    nqw = max(_last_q(kj, blk_q, blk_k, window, nq)
              - _first_q(kj, blk_q, blk_k) for kj in range(nk)) + 1
    return nkw, nqw


def _band_kj(qi, step, blk_q, blk_k, window):
    """The K tile of inner step `step` of q-block `qi`: counted up from the
    band's lowest tile; steps past the diagonal are not live."""
    return _first_k(qi, blk_q, blk_k, window) + step


def _band_qi(kj, step, steps, blk_q, blk_k, window, nq):
    """The Q tile of inner step `step` (of `steps`) of k-block `kj`: counted
    so that the last step is the band's highest tile; steps before the
    diagonal are not live."""
    return _last_q(kj, blk_q, blk_k, window, nq) - (steps - 1) + step


def window_tiles(T, window):
    """Score tiles a windowed forward call computes a head: what
    `fused_attention` tallies on the compile event, times its batch and
    heads."""
    bq, bk = _blk(T, True, window)
    return sum(_last_k(qi, bq, bk) + 1 - _first_k(qi, bq, bk, window)
               for qi in range(T // bq))


def causal_tiles(T):
    """Score tiles a causal forward call without a window computes a head:
    those that meet the triangle (what a call under a kept set computes
    too: its tiles are masked, none is skipped)."""
    bq, bk = _blk(T, True)
    return sum(_last_k(qi, bq, bk) + 1 for qi in range(T // bq))


def _dead_steps(T, blk_q, blk_k):
    """Steps of a causal call's (T / blk_q) x (T / blk_k) grid that lie
    wholly above the diagonal, a head: 28 of 64 at 8192 tokens in 1024 x
    1024 tiles, 6 of 16 at 4096, none where a row is one K block. They
    compute nothing, and every index map of the call that would move on one
    is held on the live tile beside it (`_forward`, `_bwd_specs`); a call
    that has none gets no clamp, the maps it had.
    Measured (TPU v5 lite, bf16, the kernels alone and chained on the host's
    clock, `tools/kept_set_probe.py`; chip run, PR 70), ms a call forward /
    backward, each operand's own block on every step -> held: [32, 8192,
    128] 5.314 / 9.749 -> 5.118 / 9.373, under a kept set (its tile held
    either way) 5.431 / 9.973 -> 5.302 / 9.626; [32, 4096, 128] 1.606 /
    2.850 -> 1.572 / 2.735. What the dead steps still cost once they fetch
    nothing, the live tiles on a folded grid without them: 0.09 / 0.24 of
    the 8192 call, 0.03 / 0.09 of the 4096 one."""
    return sum(T // blk_k - 1 - _last_k(qi, blk_q, blk_k)
               for qi in range(T // blk_q))


def interior_tiles(T, window=None):
    """Score tiles of a causal forward call that the causal mask cannot
    change, a head (`_causal_interior`): what `fused_attention` tallies as
    `flash_tiles_unmasked`, times its batch and heads, for the calls whose
    kernels run them without the mask (`_interior_apart`). None where a row
    is one K block, and none at a length outside the kernels' envelope,
    which the reference path runs."""
    if T % _LANES:
        return 0
    window = _window_of(window, T)
    bq, bk = _blk(T, True, window)
    return sum(_causal_interior(qi, kj, bq, bk, window)
               for qi in range(T // bq) for kj in range(T // bk))


def edge_strips(T, window=None):
    """(Edge tiles of a causal head-major forward call that run in strips,
    sub-blocks of them that are not computed), a head: what
    `fused_attention` tallies as `flash_edge_tiles_stripped` and
    `flash_subblocks_skipped`, times its batch and heads. The kernels' own
    predicates on Python ints (`_strip_side`, `_on_edge`): a row's diagonal
    tile and, under a window of whole tiles, the band's lower one, each
    short of `n (n - 1) / 2` of its `n x n` sub-blocks (6 of 16). (0, 0)
    where a row is one K block, where the call is not aligned, and at a
    length outside the kernels' envelope, which the reference path runs."""
    if T % _LANES:
        return 0, 0
    window = _window_of(window, T)
    bq, bk = _blk(T, True, window)
    side = _fwd_plan(T, bk) == "stream" and _strip_side(bq, bk, window)
    if not side:
        return 0, 0
    tiles = sum(sum(map(bool, _on_edge(qi, kj, bq, bk, window)))
                for qi in range(T // bq) for kj in range(T // bk))
    n = bq // side
    return tiles, tiles * (n * (n - 1) // 2)


def kept_pairs(T, topk):
    """Pairs (query, key) a selection of the `topk` largest below the
    diagonal keeps in a sequence of T: `min(t + 1, topk)` a row."""
    k = min(int(topk), T)
    return k * (k + 1) // 2 + (T - k) * k


def _dropout_mask(seed_ref, bh, qi, kj, shape, rate):
    """Deterministic keep-mask for one (bh, q-block, k-block) tile. Re-seeding
    per tile makes the mask independent of kernel iteration order, so the
    forward and the backward kernels all regenerate the same mask."""
    from jax.experimental.pallas import tpu as pltpu

    s = seed_ref[0, 0] * _HASH_A + bh * _HASH_B + qi
    s = s * _HASH_A + kj
    pltpu.prng_seed(s)
    bits = pltpu.prng_random_bits(shape)  # uniform int32 over full range
    # P(bits >= t) = 1 - rate  for t = -2^31 + rate * 2^32
    thresh = int(min(max(-2**31 + rate * 2**32, -2**31), 2**31 - 1))
    return bits >= jnp.int32(thresh)


# -- the heads of a grid step ------------------------------------------------
# A head-major call hands the kernels `[B*H, T, D]`: a grid step's blocks are
# one head's, whole. A token-major call hands them `[B, T, H*D]`, the
# projections' own layout: a head is a lane range, a block holds `step` heads
# of one row block, and a grid step computes them all (`_each_head`). A
# block's last dimension is a multiple of a vreg's 128 lanes (or the whole
# axis), so heads narrower than that come in groups (two of 64 lanes): a
# head's operands are its group's lanes with the other heads' zeroed (`_own`)
# where the product contracts over lanes, and its results are written under
# the same mask (`_put`, `_rmw`). At D = 64 a product already fills half of a
# 128-deep MXU pass, so the group's 128 lanes cost the pass the head's 64
# cost; what a head adds is the selects, on `[blk_q, 128]` arrays beside a
# `[blk_q, blk_k]` score tile.

class _Heads(NamedTuple):
    """How a token-major call's blocks hold heads: `total` heads of `width`
    lanes a token, `step` of them a block, in groups `lanes` wide."""
    total: int
    step: int
    width: int
    lanes: int

    @property
    def group(self):
        """Heads that share a group's lanes."""
        return self.lanes // self.width


class _Head(NamedTuple):
    """One head of a grid step, as indices into the step's blocks."""
    bh: object      # b * H + h: what the dropout mask is keyed by
    blk: object     # into a [1, rows, lanes] block: the head's group
    row: object     # into an Lse or delta block: the head's row
    stat: object    # into the row statistics in scratch
    acc: object     # into a [rows, lanes] accumulator in scratch
    lanes: object   # the group's lanes
    mask: object    # the head's lanes among its group's; None: all of them

    @property
    def whole(self):
        """The step's blocks are this head's alone (a head-major call)."""
        return self.blk == 0


def _each_head(heads, first, body):
    """`body(head)` for every head of this grid step; `first` is grid axis
    0's id (the (batch x head) row of a head-major call) or the step's first
    head of a token-major one (`_grid_ids`). The lane groups of a
    token-major block run under a `fori_loop`, a group's lanes a dynamic
    slice at a multiple of its width, and the heads of a group are unrolled
    in the loop's body with masks that are constants. What jax traces and
    Mosaic compiles is one group's instructions however many a block
    holds (all eight heads unrolled over a 512 x 2048 score tile took
    Mosaic 15 s where a head-major call takes 1); the two heads of a group
    side by side leave the scheduler one head's MXU work to put beside the
    other's VPU work, which a loop over single heads does not (chip runs,
    PR 46: the one-pass forward at seq 256 0.55 ms a call against 0.63, the
    backward 0.58 against 0.72; head-major 0.64 and 0.86)."""
    from jax.experimental import pallas as pl

    if heads is None:
        return body(_Head(first, 0, (0, 0), ..., ..., slice(None), None))

    def group(g, carry=None):
        at = g * heads.lanes
        lanes = pl.ds(at if isinstance(g, int)
                      else pl.multiple_of(at, heads.lanes), heads.lanes)
        for sub in range(heads.group):
            i = g * heads.group + sub
            mask = None
            if heads.group > 1:
                lane = lax.broadcasted_iota(jnp.int32, (1, heads.lanes), 1)
                mask = (lane >= sub * heads.width) \
                    & (lane < (sub + 1) * heads.width)
            body(_Head(first + i, (0, slice(None), lanes), (i, 0), i,
                       (slice(None), lanes), lanes, mask))

    groups = heads.step // heads.group
    if groups == 1:
        return group(0)
    lax.fori_loop(0, groups, group, None)


def _own(hd, x):
    """`x` with the lanes of the group's other heads zeroed."""
    return x if hd.mask is None else jnp.where(hd.mask, x, jnp.zeros_like(x))


def _put(hd, ref, x):
    """Write the head's lanes of its group in an output block."""
    ref[hd.blk] = x if hd.mask is None else jnp.where(hd.mask, x, ref[hd.blk])


def _rmw(hd, ref, idx, f):
    """`ref[idx] = f(ref[idx])` on the head's lanes of its group."""
    old = ref[idx]
    new = f(old)
    ref[idx] = new if hd.mask is None else jnp.where(hd.mask, new, old)


def _delta(hd, delta_ref, do, rows=_ALL):
    """rowsum(dOut * Out) of the head's rows (of `rows` of them, as `do`
    is), float32. A head-major call is
    handed it, a row of the `Lse`-shaped delta block (XLA sums over `[B*H,
    T, Dv]` beside the call). A token-major call is handed `Out`'s block in
    its place and sums here, a column, over the group's lanes of `do`, which
    holds zeros in the other heads': the sum over `[B, T, H, Dv]` would be
    an XLA op of an operand's size between the projections and the kernel,
    as the copies were (and its fusion took XLA longer to compile than the
    kernel takes Mosaic)."""
    if hd.whole:
        return delta_ref[_sub(hd.row, rows)]
    return jnp.sum(do.astype(jnp.float32)
                   * delta_ref[hd.blk].astype(jnp.float32),
                   axis=1, keepdims=True)


def _col(x):
    """A row statistic against a [blk_q, blk_k] tile."""
    return x[:, None] if x.ndim == 1 else x


def _score_tile(q_ref, k_ref, hd, sm_scale, piece, kept_ref=None):
    """The float32 scores q k^T * sm_scale of one `piece` of a tile (the
    whole `[blk_q, blk_k]` of it, or a strip's rows by the keys they see),
    the causal mask applied in-register where the piece has one (a causal
    call's edge tiles, `_on_live_tile`; every tile of a one-pass call), the
    kept set's on every piece. The dots run in the INPUT dtype (bf16 under
    AMP -> full MXU rate; the round-3 kernels upcast to f32 first,
    quartering matmul throughput) with f32 accumulation via
    preferred_element_type; sm_scale is applied to the f32 product so no
    operand precision is spent on it."""
    s = lax.dot_general(_own(hd, q_ref[_sub(hd.blk, piece.rows)]),
                        k_ref[_sub(hd.blk, piece.keys)],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
    return _masked(s, piece, kept_ref)


def _tile_dropout(seed_ref, hd, qi, kj, tile, dropout_rate):
    """`keep(piece)`: the dropout keep-mask of one piece of the (qi, kj)
    tile, its part of the one mask the `tile` (blk_q, blk_k) draws
    (`_dropout_mask`) whichever pieces it runs in: drawn where the first
    piece asks for it, once."""
    draw = functools.cache(lambda: _dropout_mask(
        seed_ref, hd.bh, qi, kj, tile, dropout_rate))

    def keep(piece):
        return draw() if piece.whole else draw()[piece.rows, piece.keys]
    return keep


def _weights_times_v(p, v_ref, hd, dropout_rate, piece, keep):
    """dropout(p) v for one piece of a tile, float32 [its rows, Dv] (the
    group's lanes: the caller keeps the head's); `keep`: `_tile_dropout`."""
    if dropout_rate:
        p = jnp.where(keep(piece), p / (1.0 - dropout_rate), 0.0)
    v = v_ref[_sub(hd.blk, piece.keys)]
    return lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _flash_fwd_onepass_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                              *, sm_scale, causal, dropout_rate, window=None,
                              heads=None, kept_ref=None):
    """A row is one K block (`_fwd_plan`): the softmax of a q-block is
    whole in its one grid step, so there is no running maximum to correct,
    nothing carried in scratch and no branch. The row statistics keep the
    score tile's layout (one value a sublane, `keepdims`), broadcast along
    lanes against the tile and the output; the one relayout is the `Lse`
    row. Bit for bit what `_flash_fwd_kernel` gives at the same tiles: its
    first step scales a zero state by `exp(NEG_INF - m) = 0`."""
    from jax.experimental import pallas as pl

    first, qi = _grid_ids(heads, tile_axes=1)
    s_shape = (q_ref.shape[1], k_ref.shape[1])

    def head(hd):
        (tile,) = _whole(causal, qi, 0, *s_shape, window)
        s = _score_tile(q_ref, k_ref, hd, sm_scale, tile, kept_ref)
        m = jnp.max(s, axis=1, keepdims=True)              # [blk_q, 1]
        p = jnp.exp(s - m)
        l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-20)
        acc = _weights_times_v(p, v_ref, hd, dropout_rate, tile,
                               _tile_dropout(seed_ref, hd, qi, 0, s_shape,
                                             dropout_rate))
        _put(hd, o_ref, (acc / l).astype(o_ref.dtype))
        lse_ref[hd.row] = (m + jnp.log(l))[:, 0]

    _each_head(heads, first, head)


# The streaming forward's row statistics in scratch are `_LANES` wide, every
# lane of a row holding the row's value (as jax's own TPU flash kernel keeps
# `m` and `l`). A 1-D `(blk_q,)` scratch wants the rows along lanes and a
# `(blk_q, 1)` one stores a lane of each vreg; both cost more than the
# products of a short tile (PERF.md section 7, PR 33).
def _lanes(x, n):
    """A lane-replicated [rows, _LANES] statistic against [rows, n]."""
    reps = -(-n // _LANES)
    if reps > 1:
        x = jnp.tile(x, (1, reps))
    return x if x.shape[1] == n else x[:, :n]


def _grid_ids(heads, tile_axes=2):
    """(what `_each_head` counts the step's heads from, the id of the
    grid's outer tile axis; and of a grid with two, the id of the inner,
    how many steps the inner has, and the outer)."""
    from jax.experimental import pallas as pl

    first = pl.program_id(0)
    ax = 1
    if heads is not None:
        first = first * heads.total + pl.program_id(1) * heads.step
        ax = 2
    if tile_axes == 1:
        return first, pl.program_id(ax)
    return (first, pl.program_id(ax), pl.program_id(ax + 1),
            lambda: pl.num_programs(ax + 1), lambda: pl.num_programs(ax))


def _flash_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_sc, l_sc, acc_sc, *,
                      sm_scale, causal, dropout_rate, window=None,
                      heads=None, kept_ref=None):
    """A row has several K blocks. K/V STREAM through the grid's innermost
    ("arbitrary") dimension: each program sees one [blk_k, D] K/V block,
    with the online-softmax state carried in VMEM scratch across kj
    iterations. VMEM per program is O(blk_q * (blk_k + D)) regardless of
    T — the previous full-K/V residency capped T*D (scoped-VMEM OOM at seq
    8192 with D=128). The running maximum and sum are [blk_q, _LANES]
    arrays, lane-replicated: the reductions of the score tile keep their
    dimension and broadcast into them along lanes, and the only relayout
    is the `Lse` row written after the last block. Under a `window` the
    inner axis counts the tiles of the q-block's band (`_band_kj`), lowest
    first: a row whose window has not reached into a tile adds `exp(0)`s to
    a state that the diagonal tile, always the last live one, scales by
    `exp(NEG_INF - m) = 0`."""
    from jax.experimental import pallas as pl

    first, qi, step, inner, _ = _grid_ids(heads)
    nk = inner()
    blk_q = q_ref.shape[1]
    blk_k = k_ref.shape[1]
    Dv = v_ref.shape[2] if heads is None else heads.lanes
    kj = step if window is None else _band_kj(qi, step, blk_q, blk_k, window)

    @pl.when(step == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def _update(pieces):
        def head(hd):
            keep = _tile_dropout(seed_ref, hd, qi, kj, (blk_q, blk_k),
                                 dropout_rate)
            for pc in pieces:       # rows of the tile, independent
                s = _score_tile(q_ref, k_ref, hd, sm_scale, pc, kept_ref)
                stat = _sub(hd.stat, pc.rows)
                m = m_sc[stat]
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - _lanes(m_new, s.shape[1]))
                alpha = jnp.exp(m - m_new)
                l_sc[stat] = l_sc[stat] * alpha \
                    + jnp.sum(p, axis=1, keepdims=True)
                _rmw(hd, acc_sc, _sub(hd.acc, pc.rows),
                     lambda acc: acc * _lanes(alpha, Dv) + _weights_times_v(
                         p, v_ref, hd, dropout_rate, pc, keep))
                m_sc[stat] = m_new

        _each_head(heads, first, head)

    # causal: blocks entirely above the diagonal contribute nothing
    _on_live_tile(_update, causal, qi, kj, blk_q, blk_k, window,
                  _interior_apart(window, kept_ref),
                  strips=None if heads else "rows")

    @pl.when(step == nk - 1)
    def _finalize():
        def head(hd):
            l = jnp.maximum(l_sc[hd.stat], 1e-20)
            _put(hd, o_ref,
                 (acc_sc[hd.acc] / _lanes(l, Dv)).astype(o_ref.dtype))
            lse_ref[hd.row] = (m_sc[hd.stat] + jnp.log(l))[:, 0]

        _each_head(heads, first, head)


def _flash_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, dq_ref, dq_sc, *, sm_scale, causal,
                     dropout_rate, window=None, heads=None, kept_ref=None,
                     several=True):
    """dQ with K/V streamed through the innermost grid dim (see
    _flash_fwd_kernel); the dQ accumulator lives in VMEM scratch. `several`:
    a row has several K blocks (one: every tile whole, as in the fused
    kernel)."""
    from jax.experimental import pallas as pl

    first, qi, step, inner, _ = _grid_ids(heads)
    nk = inner()
    blk_q = q_ref.shape[1]
    blk_k = k_ref.shape[1]
    kj = step if window is None else _band_kj(qi, step, blk_q, blk_k, window)

    @pl.when(step == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def _update(pieces):
        def head(hd):
            keep = _tile_dropout(seed_ref, hd, qi, kj, (blk_q, blk_k),
                                 dropout_rate)
            for pc in pieces:       # rows of the tile, independent
                q = _own(hd, q_ref[_sub(hd.blk, pc.rows)])
                do = _own(hd, do_ref[_sub(hd.blk, pc.rows)])   # [blk_q, D]
                lse = lse_ref[_sub(hd.row, pc.rows)]           # [blk_q]
                delta = _delta(hd, delta_ref, do, pc.rows)
                k = k_ref[_sub(hd.blk, pc.keys)]
                v = v_ref[_sub(hd.blk, pc.keys)]
                s = lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                s = _masked(s, pc, kept_ref)
                w = jnp.exp(s - lse[:, None])              # normalized weights
                dpv = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
                if dropout_rate:
                    dw = jnp.where(keep(pc), dpv / (1.0 - dropout_rate), 0.0)
                else:
                    dw = dpv
                ds = w * (dw - _col(delta)) * sm_scale
                _rmw(hd, dq_sc, _sub(hd.acc, pc.rows),
                     lambda dq: dq + lax.dot_general(
                         ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32))

        _each_head(heads, first, head)

    _on_live_tile(_update, causal, qi, kj, blk_q, blk_k, window,
                  _interior_apart(window, kept_ref),
                  strips="rows" if several and not heads else None)

    @pl.when(step == nk - 1)
    def _finalize():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dk_ref, dv_ref, dk_sc, dv_sc, *,
                      sm_scale, causal, dropout_rate, window=None,
                      q_tiles=None, heads=None, kept_ref=None, several=True):
    """dK/dV with Q/dOut/lse/delta streamed through the innermost grid
    dim (grid = (BH, kj, qi)); accumulators in VMEM scratch. Under a
    `window` the inner axis counts the Q tiles of the k-block's band
    (`_band_qi`; `q_tiles` is the row's whole count). `several`: as in
    `_flash_dq_kernel`."""
    from jax.experimental import pallas as pl

    first, kj, step, inner, _ = _grid_ids(heads)
    nq = inner()
    blk_q = q_ref.shape[1]
    blk_k = k_ref.shape[1]
    qi = step if window is None else _band_qi(kj, step, nq, blk_q, blk_k,
                                              window, q_tiles)

    @pl.when(step == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def _update(pieces):
        def head(hd):
            keep = _tile_dropout(seed_ref, hd, qi, kj, (blk_q, blk_k),
                                 dropout_rate)
            for pc in pieces:       # keys of the tile, the rows on them
                k = k_ref[_sub(hd.blk, pc.keys)]           # [blk_k, D]
                v = v_ref[_sub(hd.blk, pc.keys)]
                q = _own(hd, q_ref[_sub(hd.blk, pc.rows)])
                do = _own(hd, do_ref[_sub(hd.blk, pc.rows)])
                lse = lse_ref[_sub(hd.row, pc.rows)]
                delta = _delta(hd, delta_ref, do, pc.rows)
                s = lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                s = _masked(s, pc, kept_ref)
                w = jnp.exp(s - lse[:, None])              # [blk_q, blk_k]
                dpv = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
                if dropout_rate:
                    on = keep(pc)
                    w_drop = jnp.where(on, w / (1.0 - dropout_rate), 0.0)
                    dw = jnp.where(on, dpv / (1.0 - dropout_rate), 0.0)
                else:
                    w_drop, dw = w, dpv
                _rmw(hd, dv_sc, _sub(hd.acc, pc.keys),
                     lambda dv: dv + lax.dot_general(
                         w_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32))
                ds = w * (dw - _col(delta)) * sm_scale
                _rmw(hd, dk_sc, _sub(hd.acc, pc.keys),
                     lambda dk: dk + lax.dot_general(
                         ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32))

        _each_head(heads, first, head)

    # causal: q blocks strictly above this k block see none of it
    _on_live_tile(_update, causal, qi, kj, blk_q, blk_k, window,
                  _interior_apart(window, kept_ref),
                  strips="keys" if several and not heads else None)

    @pl.when(step == nq - 1)
    def _finalize():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _flash_bwd_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                      *dq_sc, sm_scale, causal, dropout_rate, window=None,
                      q_tiles=None, heads=None, kept_ref=None):
    """dQ, dK and dV from one pass: `_flash_dkv_kernel` (grid (BH, kj, qi),
    q innermost) with one product more, this tile's share of dQ from the
    `ds` it has already formed. Without `dq_sc` a row is one K block and
    the share is the q-block's whole dQ, written straight to its output
    block. With it a row has several: the row's dQ accumulates over kj in
    float32 `[T, D]` scratch (in ascending kj for every q-block, as
    `_flash_dq_kernel` adds them) and is cast and written after the row's
    last tile. Under a `window` the inner axis counts the Q tiles of the
    k-block's band, as in `_flash_dkv_kernel`."""
    from jax.experimental import pallas as pl

    dq_sc = dq_sc[0] if dq_sc else None
    first, kj, step, inner, outer = _grid_ids(heads)
    nk = outer()
    nq = inner()
    blk_q = q_ref.shape[1]
    blk_k = k_ref.shape[1]
    qi = step if window is None else _band_qi(kj, step, nq, blk_q, blk_k,
                                              window, q_tiles)

    @pl.when(step == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    if dq_sc is not None:
        @pl.when((kj == 0) & (step == 0))
        def _init_row():
            dq_sc[...] = jnp.zeros_like(dq_sc)

    def _update(pieces):
        def head(hd):
            keep = _tile_dropout(seed_ref, hd, qi, kj, (blk_q, blk_k),
                                 dropout_rate)
            shares = []
            for pc in pieces:       # keys of the tile, the rows on them
                k = k_ref[_sub(hd.blk, pc.keys)]           # [blk_k, D]
                v = v_ref[_sub(hd.blk, pc.keys)]
                q = _own(hd, q_ref[_sub(hd.blk, pc.rows)])
                do = _own(hd, do_ref[_sub(hd.blk, pc.rows)])
                lse = lse_ref[_sub(hd.row, pc.rows)]
                delta = _delta(hd, delta_ref, do, pc.rows)
                s = lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                s = _masked(s, pc, kept_ref)
                w = jnp.exp(s - lse[:, None])              # [blk_q, blk_k]
                dpv = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
                if dropout_rate:
                    on = keep(pc)
                    w_drop = jnp.where(on, w / (1.0 - dropout_rate), 0.0)
                    dw = jnp.where(on, dpv / (1.0 - dropout_rate), 0.0)
                else:
                    w_drop, dw = w, dpv
                _rmw(hd, dv_sc, _sub(hd.acc, pc.keys),
                     lambda dv: dv + lax.dot_general(
                         w_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32))
                ds = (w * (dw - _col(delta)) * sm_scale).astype(q.dtype)
                _rmw(hd, dk_sc, _sub(hd.acc, pc.keys),
                     lambda dk: dk + lax.dot_general(
                         ds, q, (((0,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32))
                shares.append(ds)
            # dQ: a row's shares of the tile's key strips, gathered in
            # ascending key order into one product's contraction, which is
            # the whole tile's product without its zero terms
            for rows, ds, keys in _row_shares(pieces, shares):
                if keys != _ALL:
                    k = k_ref[_sub(hd.blk, keys)]
                dq = lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                if dq_sc is None:
                    _put(hd, dq_ref, dq.astype(dq_ref.dtype))
                    continue
                first_row, n = (0, blk_q) if rows == _ALL else \
                    (rows.start, rows.stop - rows.start)
                at = qi * blk_q + first_row if first_row else qi * blk_q
                _rmw(hd, dq_sc, (pl.ds(pl.multiple_of(at, n), n), hd.lanes),
                     lambda acc: acc + dq)

        _each_head(heads, first, head)

    # with one K block a row (kj == 0) every tile is live, and none interior
    several = dq_sc is not None
    _on_live_tile(_update, causal, qi, kj, blk_q, blk_k, window,
                  several and _interior_apart(window, kept_ref),
                  strips="keys" if several and not heads else None)

    @pl.when(step == nq - 1)
    def _finalize():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)

    if dq_sc is not None:
        @pl.when((kj == nk - 1) & (step == nq - 1))
        def _finalize_row():
            dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def _seed_arr(seed):
    return jnp.asarray(seed, jnp.int32).reshape(1, 1)


def _compiler_params(carried=1, vmem_bytes=None, heads=None):
    """The last `carried` grid dims iterate sequentially (they carry
    scratch accumulators); the ones before are parallel. 0: the one-pass
    forward, a two-axis grid whose steps share nothing. 1: a three-axis
    grid whose innermost dim carries the state of a row (the streaming
    forward, the split backward pair, the fused backward where a row is
    one K block). 2: the middle one carries an accumulator too (the fused
    backward's dQ row), and the kernel asks for the scoped VMEM that row
    needs itself, `vmem_bytes` (`_fused_bwd_vmem`): on this call alone, not
    through the executor's option for the whole step
    (core/executor.py::resolve_compiler_options, which a caller under plain
    `jax.jit` does not have either). A token-major call (`heads`) has one
    parallel axis more in front, batch and head block apart, and always
    says what its blocks of several heads need."""
    from jax.experimental.pallas import tpu as pltpu
    semantics = {0: ("parallel", "parallel"),
                 1: ("parallel", "parallel", "arbitrary"),
                 2: ("parallel", "arbitrary", "arbitrary")}[carried]
    if heads is not None:
        semantics = ("parallel",) + semantics
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=vmem_bytes)


def _window_of(window, T):
    """A window that reaches over the whole row is plain causal."""
    return None if window is None or window >= T else int(window)


def _named(name, window, kept=None):
    """Windowed calls under names of their own (`swa_...`), and so the
    calls that take a kept set (`dsa_...`)."""
    if kept is not None:
        return "dsa_" + name
    return name if window is None else "swa_" + name


def _check_kept(kept, q, causal, window, token_major):
    """A kept set is int8 `[B, T, T]`, one for all heads of a batch row, on
    a causal head-major call without a window."""
    if kept is None:
        return
    B, _, T, _ = _shape_of(q, token_major)
    if not causal or window is not None or token_major:
        raise ValueError(
            "flash attention takes a kept set on a causal call of "
            "[batch, heads, seq, head_dim] operands without a window; got "
            f"causal = {causal}, window = {window}, token-major = "
            f"{token_major}")
    if kept.shape != (B, T, T) or kept.dtype != jnp.int8:
        raise ValueError(
            f"flash attention needs a kept set of int8 [{B}, {T}, {T}] "
            f"(batch, query, key), got {kept.dtype} {tuple(kept.shape)}")


def _kept_vmem(BQ, BK):
    """Bytes of scoped VMEM a kept set's tile adds to a grid step: the int8
    block double-buffered and its int32 widening."""
    return (2 + 4) * BQ * BK


def _kept_spec(H, BQ, BK, at_q, at_k):
    """The kept set's block of a grid step: the (q-block, k-block) tile of
    the row's batch (grid axis 0 is the (batch x head) row) that the call's
    own maps `at_q` and `at_k` name. Those hold a step wholly above the
    diagonal on the live tile beside it in grid order (`_dead_steps`), so
    nothing of the set is fetched for a step that computes nothing and has
    no products to hide a fetch behind.
    Measured (TPU v5 lite, [32, 8192, 128] bf16, 1024 x 1024 tiles, 28 of a
    head's 64 steps dead; chip runs, PR 68): with the set's own tile on
    every step the forward is 6.477 ms a call and the fused backward 11.029
    in `keye_vl_2_30b_a3b.s8192`'s trace, held 5.537 and 9.799; alone and
    chained on the host's clock (`tools/kept_set_probe.py`) 6.31 / 11.21
    against 5.43 / 9.96, and 5.33 / 9.77 without a set. Applying the tile
    costs nothing (5.15 / 9.93 applied from one tile never fetched again)."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((1, BQ, BK), lambda *g: (
        g[0] // H, at_q(*g)[1], at_k(*g)[1]))


def _takes_kept(kernel, at):
    """`kernel` with the kept set's ref, operand `at` of the call, handed
    over by name: the kernels' positional refs stay what they are."""
    def with_kept(*refs, **kw):
        return kernel(*refs[:at], *refs[at + 1:], kept_ref=refs[at], **kw)
    return with_kept


class _Rows(NamedTuple):
    """Where a call's rows lie in HBM, for its block specs. Head-major
    (`heads` None): `[B*H, T, D]`, grid axis 0 the (batch x head) row.
    Token-major: `[B, T, H*D]`, grid axes 0 and 1 batch and head block, a
    block `heads.step` heads wide at lane block `g[1]`. `Lse` and delta are
    `[B*H, 1, T]` either way: a token-major block holds its heads' rows."""
    B: int
    H: int
    heads: object

    @property
    def axes(self):
        return 1 if self.heads is None else 2

    @property
    def grid(self):
        if self.heads is None:
            return (self.B * self.H,)
        return (self.B, self.H // self.heads.step)

    def of(self, x):
        """`x` as the kernels read it: a free reshape either way."""
        if self.heads is None:
            return x.reshape(self.B * self.H, x.shape[2], x.shape[3])
        return x.reshape(self.B, x.shape[1], self.H * x.shape[3])

    def lanes(self, x3):
        """Lanes of a block of `x3` (`of` an operand): its heads' widths."""
        return x3.shape[2] // (1 if self.heads is None else self.grid[1])

    def blk(self, g, tile):
        return (g[0], tile, 0 if self.heads is None else g[1])

    def row(self, g, tile):
        if self.heads is None:
            return (g[0], 0, tile)
        return (g[0] * self.grid[1] + g[1], 0, tile)

    def row_block(self, BQ):
        return (1 if self.heads is None else self.heads.step, 1, BQ)


def _token_major_heads(H, D, need):
    """How a token-major call's blocks hold its H heads of D lanes. A group
    is the fewest heads whose lanes are whole vregs (one head of 128 or
    256 lanes, two of 64), or all of them where H * D has no such part (a
    block is then the whole axis). A grid step computes as many groups as
    divide H and whose blocks, scratch and score temporaries
    `need(lanes)` are within the scoped VMEM every call asks for anyway, one
    group where nothing is: fewer grid steps (a step costs ~0.2 us beyond
    its work) and longer contiguous rows a DMA. Returns that and the scoped
    VMEM the call asks for: its need, the floor at the least."""
    lanes = math.lcm(D, _LANES)
    if (H * D) % lanes:
        lanes = H * D
    group = lanes // D
    options = [n for n in range(group, H + 1, group) if H % n == 0]
    fits = [n for n in options if need(n * D) <= _SCOPED_VMEM_FLOOR_BYTES]
    step = max(fits) if fits else options[0]
    return (_Heads(H, step, D, lanes),
            max(need(step * D), _SCOPED_VMEM_FLOOR_BYTES))


def _fwd_vmem(lanes, heads, BQ, BK, itemsize, stream):
    """Bytes of scoped VMEM a forward step of `heads` heads needs: q, o, k
    and v blocks double-buffered, the streaming state, the score
    temporaries of one head."""
    blocks = 2 * itemsize * 2 * (BQ + BK) * lanes
    state = 4 * BQ * (lanes + 2 * heads * _LANES) if stream else 0
    return blocks + state + _SCORE_TILES * 4 * BQ * BK


def _shape_of(q, token_major):
    """(B, H, T, D) of a `[B, H, T, D]` or a `[B, T, H, D]` operand."""
    if token_major:
        B, T, H, D = q.shape
        return B, H, T, D
    return q.shape


class _Plan(NamedTuple):
    """What a call's shapes (and a test's overrides) decide before its
    kernels are built: the tiles, the kernel ("onepass" | "stream" of the
    forward, "fused" | "split" of the backward), how a token-major call's
    blocks hold heads, the scoped VMEM it asks for, whether the kernels are
    interpreted, and `built_with`, the module's predicates and rules a
    kernel's body is traced through, as they stand (`_plan`). Hashable: a
    call's kernels are traced and lowered once for each plan and each set of
    operand shapes and attributes (`_jitted_forward`, `_jitted_backward`),
    not once a call; a test or a probe that stands one of those functions in
    for another gets kernels built anew."""
    tiles: tuple
    kernel: str
    heads: object = None
    vmem: object = None
    interpret: bool = False
    built_with: tuple = ()


def _plan(tiles, kernel, heads=None, vmem=None):
    return _Plan(tiles, kernel, heads, vmem, _kernels.interpret(), (
        _STRIP_TILE, _strip_side, _on_edge, _causal_live, _causal_interior,
        _interior_apart, _apply_causal_mask, _apply_kept, _dropout_mask,
        _dead_steps, _kept_spec, _grid_ids))


def _flash_forward(q, k, v, causal, sm_scale, dropout_rate=0.0, seed=0,
                   window=None, token_major=False, kept=None):
    B, H, T, D = _shape_of(q, token_major)
    window = _window_of(window, T)
    BQ, BK = _blk(T, causal, window)
    kernel = _fwd_plan(T, BK)
    if kept is not None:
        _check_kept(kept, q, causal, window, token_major)
        vmem = _fwd_vmem(D, 1, BQ, BK, q.dtype.itemsize, kernel == "stream") \
            + _kept_vmem(BQ, BK)
        return _jitted_forward(
            q, k, v, jnp.asarray(seed, jnp.int32), causal, sm_scale,
            dropout_rate, window, _plan(
                (BQ, BK), kernel, vmem=max(vmem, _SCOPED_VMEM_FLOOR_BYTES)),
            kept)
    if not token_major:
        return _jitted_forward(
            q, k, v, jnp.asarray(seed, jnp.int32), causal, sm_scale,
            dropout_rate, window, _plan((BQ, BK), kernel))

    def need(lanes):
        return _fwd_vmem(lanes, lanes // D, BQ, BK, q.dtype.itemsize,
                         kernel == "stream")
    heads, vmem = _token_major_heads(H, D, need)
    return _jitted_forward(
        q, k, v, jnp.asarray(seed, jnp.int32), causal, sm_scale,
        dropout_rate, window, _plan((BQ, BK), kernel, heads, vmem))


def _forward(q, k, v, seed, causal, sm_scale, dropout_rate, window, plan,
             kept=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (BQ, BK), heads, vmem = plan.tiles, plan.heads, plan.vmem
    B, H, T, _ = _shape_of(q, heads is not None)
    rows = _Rows(B, H, heads)
    q3, k3, v3 = rows.of(q), rows.of(k), rows.of(v)
    attrs = dict(sm_scale=sm_scale, causal=causal, dropout_rate=dropout_rate)
    if window is not None:
        attrs["window"] = window
    if heads is not None:
        attrs["heads"] = heads
    if plan.kernel == "onepass":
        # its name holds `flash_fwd`: the benchmark's metrics of that name
        # read it as they read the streaming kernel
        name, grid, carried = "flash_fwd_onepass", (T // BQ,), 0
        kernel = functools.partial(_flash_fwd_onepass_kernel, **attrs)
        scratch = []
    else:
        steps = T // BK if window is None else \
            _band_steps(T, BQ, BK, window)[0]
        name, grid, carried = "flash_fwd", (T // BQ, steps), 1
        kernel = functools.partial(_flash_fwd_kernel, **attrs)
        stat = (BQ, _LANES) if heads is None else (heads.step, BQ, _LANES)
        scratch = [pltpu.VMEM(stat, jnp.float32),
                   pltpu.VMEM(stat, jnp.float32),
                   pltpu.VMEM((BQ, rows.lanes(v3)), jnp.float32)]
    ax = rows.axes
    # a step past the diagonal tile (a band's spare one, a causal grid's
    # dead ones) stays on it, so nothing is fetched for it
    held = window is not None or (causal and _dead_steps(T, BQ, BK))

    def at_q(*g):
        return rows.blk(g, g[ax])

    # the one-pass grid has no kj axis: its one K block is block 0
    def at_k(*g):
        if carried == 0:
            return rows.blk(g, 0)
        kj = g[ax + 1] if window is None else \
            _band_kj(g[ax], g[ax + 1], BQ, BK, window)
        if held:
            kj = jnp.minimum(kj, _last_k(g[ax], BQ, BK))
        return rows.blk(g, kj)

    in_specs = [
        pl.BlockSpec((1, 1), lambda *g: (0, 0)),
        pl.BlockSpec((1, BQ, rows.lanes(q3)), at_q),
        pl.BlockSpec((1, BK, rows.lanes(k3)), at_k),
        pl.BlockSpec((1, BK, rows.lanes(v3)), at_k),
    ]
    operands = (_seed_arr(seed), q3, k3, v3)
    if kept is not None:
        kernel = _takes_kept(kernel, len(operands))
        in_specs.append(_kept_spec(H, BQ, BK, at_q, at_k))
        operands += (kept,)
    out, lse = pl.pallas_call(
        kernel,
        grid=rows.grid + grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, BQ, rows.lanes(v3)), at_q),
            pl.BlockSpec(rows.row_block(BQ), lambda *g: rows.row(g, g[ax])),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(v3.shape, q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(carried, vmem, heads),
        interpret=plan.interpret,
        name=_named(name, window, kept),
    )(*operands)
    return out.reshape(v.shape), lse


# A call under a `jax.jit` of its own, the plan and the attributes static:
# the 18 attention blocks of a transformer step hold two forward and two
# backward kernels between them (causal or not), Ouro's sixteen one of each,
# and jax traces and lowers a jitted function once for each signature.
# Token-major calls since PR 46. Head-major ones since PR 72, whose strip
# bodies made a kernel's trace and lowering 2.6 times what it was: inline,
# the sixteen call sites of `ouro_2_6b.bs1` read `first_step_trace_s.train`
# 5.58 -> 15.36 s and `first_step_lower_s.train` 5.27 -> 7.56 (chip run, PR
# 72, call 3). XLA inlines the call: the compiled step is the one it was.
_jitted_forward = jax.jit(_forward, static_argnums=(4, 5, 6, 7, 8))


def _flash_backward(q, k, v, o, lse, g, causal, sm_scale, dropout_rate, seed,
                    window=None, token_major=False, kept=None):
    B, H, T, D = _shape_of(q, token_major)
    Dv = v.shape[-1]
    window = _window_of(window, T)
    BQ, BK = _blk(T, causal, window)
    itemsize = q.dtype.itemsize
    if kept is not None:
        _check_kept(kept, q, causal, window, token_major)
        vmem = _fused_bwd_vmem(T if T != BK else BQ, D, Dv, BQ, BK, itemsize) \
            + _kept_vmem(BQ, BK)
        return _jitted_backward(
            q, k, v, o, lse, g, jnp.asarray(seed, jnp.int32), causal,
            sm_scale, dropout_rate, window, _plan(
                (BQ, BK), _bwd_plan(T, D, Dv, BQ, BK, itemsize), vmem=vmem),
            kept)
    if not token_major:
        return _jitted_backward(
            q, k, v, o, lse, g, jnp.asarray(seed, jnp.int32), causal,
            sm_scale, dropout_rate, window, _plan(
                (BQ, BK), _bwd_plan(T, D, Dv, BQ, BK, itemsize)))

    # a row of one K block keeps a q-block's dQ, not the row's; `Out`'s
    # block comes in beside dOut's (`_delta`)
    def need(lanes):
        return _fused_bwd_vmem(T if T != BK else BQ, lanes, lanes, BQ, BK,
                               itemsize) + 2 * itemsize * BQ * lanes
    heads, vmem = _token_major_heads(H, D, need)
    lanes = heads.step * D
    return _jitted_backward(
        q, k, v, o, lse, g, jnp.asarray(seed, jnp.int32), causal, sm_scale,
        dropout_rate, window, _plan(
            (BQ, BK), _bwd_plan(T, lanes, lanes, BQ, BK, itemsize), heads,
            vmem))


def _backward(q, k, v, o, lse, g, seed, causal, sm_scale, dropout_rate,
              window, plan, kept=None):
    heads = plan.heads
    B, H, _, _ = _shape_of(q, heads is not None)
    rows = _Rows(B, H, heads)
    q3, k3 = (rows.of(x) for x in (q, k))
    v3, o3, g3 = (rows.of(x) for x in (v, o, g))
    if heads is None:
        delta = jnp.sum(g3.astype(jnp.float32) * o3.astype(jnp.float32),
                        axis=-1)[:, None, :]
    else:
        delta = o3      # the kernels sum a head's dOut * Out themselves
    run = _flash_bwd_fused if plan.kernel == "fused" else _flash_bwd_split
    attrs = dict(sm_scale=sm_scale, causal=causal, dropout_rate=dropout_rate)
    if window is not None:
        attrs["window"] = window
    if heads is not None:
        attrs["heads"] = heads
    args = (_seed_arr(seed), q3, k3, v3, g3, lse, delta)
    grads = run(args if kept is None else args + (kept,), rows, plan, attrs)
    return tuple(d.reshape(x.shape) for d, x in zip(grads, (q, k, v)))


_jitted_backward = jax.jit(_backward, static_argnums=(7, 8, 9, 10, 11))


def _bwd_specs(rows, T, BQ, BK, lanes, lanes_v, q_axis, causal, window):
    """Block specs of (seed, q, k, v, dO, lse, delta or Out) for a backward grid
    (rows.., ., .) whose q-block index is the first (`q_axis` 1) or the
    second (2) of the two tile axes and whose k-block index is the other;
    and the index maps of a q and a k block. Blocks of q and k are `lanes`
    wide, of v and dO `lanes_v`. Where the inner axis has steps that compute
    nothing (under a `window` it counts the tiles of a band and has spare
    ones; on a `causal` grid of rows `T` long those above the diagonal,
    `_dead_steps`) the inner block's map stays on the nearest live tile, so
    nothing is fetched for them."""
    from jax.experimental import pallas as pl

    if window is not None:
        q_steps = _band_steps(T, BQ, BK, window)[1]
    held = window is not None or (causal and _dead_steps(T, BQ, BK))
    first = rows.axes - 1          # tile axis `a` is grid axis `first + a`

    def q_of(g):
        outer, inner = g[first + 1], g[first + 2]
        if q_axis == 1:
            return outer
        qi = inner if window is None else \
            _band_qi(outer, inner, q_steps, BQ, BK, window, T // BQ)
        return jnp.maximum(qi, _first_q(outer, BQ, BK)) if held else qi

    def k_of(g):
        outer, inner = g[first + 1], g[first + 2]
        if q_axis == 2:
            return outer
        kj = inner if window is None else \
            _band_kj(outer, inner, BQ, BK, window)
        return jnp.minimum(kj, _last_k(outer, BQ, BK)) if held else kj

    def at_q(*g):
        return rows.blk(g, q_of(g))

    def at_k(*g):
        return rows.blk(g, k_of(g))

    def row_q(*g):
        return rows.row(g, q_of(g))

    return [
        pl.BlockSpec((1, 1), lambda *g: (0, 0)),
        pl.BlockSpec((1, BQ, lanes), at_q),
        pl.BlockSpec((1, BK, lanes), at_k),
        pl.BlockSpec((1, BK, lanes_v), at_k),
        pl.BlockSpec((1, BQ, lanes_v), at_q),
        pl.BlockSpec(rows.row_block(BQ), row_q),
        # delta's rows; `Out`'s block of a token-major call (`_delta`)
        pl.BlockSpec(rows.row_block(BQ), row_q) if rows.heads is None
        else pl.BlockSpec((1, BQ, lanes_v), at_q),
    ], at_q, at_k


def _kept_of(args):
    """The kept set, the operand after a backward call's seven, or None."""
    return args[7] if len(args) > 7 else None


def _flash_bwd_fused(args, rows, plan, attrs):
    """One call for dQ, dK and dV. Its name holds both `flash_dq` and
    `flash_dkv`: the benchmark's metrics of those names each read it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q3, k3, v3 = args[1:4]
    T = q3.shape[1]
    (BQ, BK), heads = plan.tiles, plan.heads
    lanes, lanes_v = rows.lanes(q3), rows.lanes(v3)
    window = attrs.get("window")
    in_specs, at_q, at_k = _bwd_specs(
        rows, T, BQ, BK, lanes, lanes_v, q_axis=2, causal=attrs["causal"],
        window=window)
    steps = T // BQ
    if window is not None:
        steps = _band_steps(T, BQ, BK, window)[1]
        attrs = dict(attrs, q_tiles=T // BQ)
    kernel = functools.partial(_flash_bwd_kernel, **attrs)
    kept = _kept_of(args)
    if kept is not None:
        kernel = _takes_kept(kernel, 7)
        in_specs.append(_kept_spec(rows.H, BQ, BK, at_q, at_k))
    scratch = [pltpu.VMEM((BK, lanes), jnp.float32),
               pltpu.VMEM((BK, lanes_v), jnp.float32)]
    if T == BK:
        dq_spec = pl.BlockSpec((1, BQ, lanes), at_q)
        params = _compiler_params(vmem_bytes=plan.vmem, heads=heads)
    else:
        # the row's dQ stays in VMEM over both inner grid axes
        dq_spec = pl.BlockSpec((1, T, lanes), lambda *g: rows.blk(g, 0))
        scratch.append(pltpu.VMEM((T, lanes), jnp.float32))
        params = _compiler_params(
            carried=2, heads=heads,
            vmem_bytes=plan.vmem or _fused_bwd_vmem(
                T, lanes, lanes_v, BQ, BK, q3.dtype.itemsize))
    return pl.pallas_call(
        kernel,
        grid=rows.grid + (T // BK, steps),
        in_specs=in_specs,
        out_specs=[dq_spec, pl.BlockSpec((1, BK, lanes), at_k),
                   pl.BlockSpec((1, BK, lanes_v), at_k)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q3, k3, v3)],
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=plan.interpret,
        name=_named("flash_dq_flash_dkv", window, kept),
    )(*args)


def _flash_bwd_split(args, rows, plan, attrs):
    """dQ gridded over query blocks, then dK/dV over key blocks: each
    recomputes the score tiles. For rows whose dQ the fused kernel cannot
    keep resident, and the tests' oracle for the fused kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q3, k3, v3 = args[1:4]
    T = q3.shape[1]
    (BQ, BK), heads = plan.tiles, plan.heads
    lanes, lanes_v = rows.lanes(q3), rows.lanes(v3)
    window = attrs.get("window")
    k_steps, q_steps = (T // BK, T // BQ) if window is None else \
        _band_steps(T, BQ, BK, window)
    # a token-major plan's VMEM counts a resident dQ row the pair does
    # not keep: more than it needs, within the budget
    params = _compiler_params(
        vmem_bytes=plan.vmem and min(plan.vmem, _VMEM_BUDGET_BYTES),
        heads=heads)
    in_specs, at_q, at_k = _bwd_specs(
        rows, T, BQ, BK, lanes, lanes_v, q_axis=1, causal=attrs["causal"],
        window=window)
    kept = _kept_of(args)

    def kernel_of(body, **more):
        if T == BK:
            more["several"] = False
        body = functools.partial(body, **attrs, **more)
        return body if kept is None else _takes_kept(body, 7)

    if kept is not None:
        in_specs.append(_kept_spec(rows.H, BQ, BK, at_q, at_k))
    dq = pl.pallas_call(
        kernel_of(_flash_dq_kernel),
        grid=rows.grid + (T // BQ, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, BQ, lanes), at_q),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        scratch_shapes=[pltpu.VMEM((BQ, lanes), jnp.float32)],
        compiler_params=params,
        interpret=plan.interpret,
        name=_named("flash_dq", window, kept),
    )(*args)
    in_specs, at_q, at_k = _bwd_specs(
        rows, T, BQ, BK, lanes, lanes_v, q_axis=2, causal=attrs["causal"],
        window=window)
    if kept is not None:
        in_specs.append(_kept_spec(rows.H, BQ, BK, at_q, at_k))
    dk, dv = pl.pallas_call(
        kernel_of(_flash_dkv_kernel,
                  **({} if window is None else {"q_tiles": T // BQ})),
        grid=rows.grid + (T // BK, q_steps),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, BK, lanes), at_k),
                   pl.BlockSpec((1, BK, lanes_v), at_k)],
        out_shape=[jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
        scratch_shapes=[pltpu.VMEM((BK, lanes), jnp.float32),
                        pltpu.VMEM((BK, lanes_v), jnp.float32)],
        compiler_params=params,
        interpret=plan.interpret,
        name=_named("flash_dkv", window, kept),
    )(*args)
    return dq, dk, dv


def _check_window(window, causal):
    """A window is a whole number of keys, at least the query's own, below a
    causal diagonal."""
    if window is None:
        return
    if not causal:
        raise ValueError(
            f"flash attention takes a window ({window}) on a causal call "
            f"only: key j is visible to query i iff 0 <= i - j < window")
    if int(window) != window or window < 1:
        raise ValueError(
            f"flash attention needs a window of at least 1 key (the query's "
            f"own position), a whole number; got window = {window!r}")


def _pallas_ok(q, dropout_rate=0.0, v=None, window=None, token_major=False):
    """Kernel or reference? The reference is a CPU-only path; on the TPU
    a shape outside the kernels' envelope raises instead of quietly
    materializing the [T, T] scores. `v` where its heads have a width of
    their own (`Dv`; the query's `D` otherwise). A `window` does not narrow
    the envelope (any W >= 1 runs, aligned to a tile or not); it is named
    in the message so that a refused shape is not put down to it. A
    token-major call's heads are lane ranges of one width, the values' as
    the queries'."""
    B, H, T, D = _shape_of(q, token_major)
    Dv = D if v is None else v.shape[-1]
    supported = T % 128 == 0 and D <= 256 and Dv <= 256
    needs = "T % 128 == 0, D <= 256 and Dv <= 256"
    if token_major:
        supported = supported and Dv == D
        needs += (", and of [batch, seq, heads, head_dim] operands value "
                  "heads as wide as the query's (Dv == D)")
    if _kernels.on_chip():
        if not supported:
            raise ValueError(
                f"flash attention on the {jax.default_backend()!r} backend "
                f"needs {needs}, got q shape "
                f"{q.shape} (query/key heads of D = {D}) and value heads of "
                f"Dv = {Dv}"
                + ("" if window is None else
                   f"; the window of {window} is not why: any window of at "
                   f"least 1 runs at a supported shape"))
        return True
    if not _kernels.interpret():
        return False
    if dropout_rate:
        return False  # pltpu.prng_* has no interpreter implementation
    return supported


# ---------------------------------------------------------------------------
# public entry: custom_vjp so program autodiff gets the Pallas backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_out_lse(q, k, v, seed, causal, sm_scale, dropout_rate,
                   window=None, token_major=False):
    """The forward kernel's two results: `out` in the operands' layout
    ([B, H, T, Dv], or [B, T, H, Dv] of token-major ones) and the rows'
    log-sum-exp, float32 [B*H, 1, T] (the layout the backward kernels read;
    no gradient flows through it)."""
    return _flash_forward(q, k, v, causal, sm_scale, dropout_rate, seed,
                          window, token_major)


def _fol_fwd(q, k, v, seed, causal, sm_scale, dropout_rate, window=None,
             token_major=False):
    out, lse = _flash_forward(q, k, v, causal, sm_scale, dropout_rate, seed,
                              window, token_major)
    return (out, lse), (q, k, v, out, lse, seed)


def _fol_bwd(causal, sm_scale, dropout_rate, window, token_major, res, g):
    q, k, v, o, lse, seed = res
    dq, dk, dv = _flash_backward(q, k, v, o, lse, g[0], causal, sm_scale,
                                 dropout_rate, seed, window, token_major)
    return dq, dk, dv, np.zeros(jnp.shape(seed), jax.dtypes.float0)


_flash_out_lse.defvjp(_fol_fwd, _fol_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dsa_out_lse(q, k, v, kept, sm_scale):
    """`_flash_out_lse` of a causal head-major call under a kept set (int8
    `[B, T, T]`, no gradient): the `dsa_` kernels, forward and backward."""
    return _flash_forward(q, k, v, True, sm_scale, kept=kept)


def _dol_fwd(q, k, v, kept, sm_scale):
    out, lse = _flash_forward(q, k, v, True, sm_scale, kept=kept)
    return (out, lse), (q, k, v, kept, out, lse)


def _dol_bwd(sm_scale, res, g):
    q, k, v, kept, o, lse = res
    dq, dk, dv = _flash_backward(q, k, v, o, lse, g[0], True, sm_scale, 0.0,
                                 0, kept=kept)
    return dq, dk, dv, np.zeros(kept.shape, jax.dtypes.float0)


_dsa_out_lse.defvjp(_dol_fwd, _dol_bwd)


def _reference(q, k, v, causal, sm_scale, dropout_rate, seed, window,
               token_major, kept=None):
    """`_attention_reference` on operands of either layout: token-major
    ones are transposed to the reference's `[B, H, T, D]` and its result
    back, so both layouts draw one mask."""
    if not token_major:
        return _attention_reference(q, k, v, causal, sm_scale, dropout_rate,
                                    seed, window, kept)
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    return _attention_reference(q, k, v, causal, sm_scale, dropout_rate,
                                seed, window).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, seed, causal=False, sm_scale=1.0,
                    dropout_rate=0.0, window=None, token_major=False,
                    kept=None):
    """seed: int32 scalar (traced) driving attention-weight dropout. For
    direct callers (tools, tests): under `jax.grad` the forward kernel is
    the residual pass and the backward kernels follow. `window`: see the
    module's docstring. `token_major`: the operands (and the result) are
    `[B, T, H, D]`, not `[B, H, T, D]`. `kept`: int8 `[B, T, T]`, the keys
    each query keeps of those below the diagonal (no dropout with it)."""
    _check_window(window, causal)
    if kept is not None:
        _check_kept(kept, q, causal, window, token_major)
        if _pallas_ok(q, dropout_rate, v):
            return _dsa_out_lse(q, k, v, kept, sm_scale)[0]
        return _reference(q, k, v, causal, sm_scale, dropout_rate, seed,
                          window, token_major, kept)
    if _pallas_ok(q, dropout_rate, v, window, token_major):
        return _flash_out_lse(q, k, v, seed, causal, sm_scale,
                              dropout_rate, window, token_major)[0]
    return _reference(q, k, v, causal, sm_scale, dropout_rate, seed, window,
                      token_major)


LAYOUTS = ("BHTD", "BTHD")


def _token_major(ctx):
    """Whether the op's operands are `[batch, seq, heads, head_dim]`
    (`layout` "BTHD") and not `[batch, heads, seq, head_dim]` ("BHTD")."""
    layout = ctx.attr("layout", "BHTD")
    if layout not in LAYOUTS:
        raise ValueError(f"fused_attention: layout {layout!r} is none of "
                         f"{LAYOUTS}")
    return layout == "BTHD"


def _attrs(ctx, Q):
    """(sm_scale, causal, dropout rate, window) of a fused_attention op."""
    rate = 0.0 if ctx.attr("is_test", False) else ctx.attr("dropout_rate", 0.0)
    causal, window = ctx.attr("causal", False), ctx.attr("window")
    _check_window(window, causal)
    return (ctx.attr("sm_scale", 1.0 / math.sqrt(Q.shape[-1])), causal,
            float(rate), window)


def _dropout_seed(ctx, rate):
    """The int32 the kernels seed their masks from. The forward rule and
    the grad op both take it from here, from the same per-op key
    (core/lowering.py folds the forward op's index into the step's key for
    both), so the backward kernels regenerate the mask the loss saw."""
    if rate and ctx.key is not None:
        return jax.random.key_data(ctx.key).reshape(-1)[0].astype(jnp.int32)
    return jnp.int32(0)


def _fused_attention_infer(ctx, structs):
    """Build-time shapes without a trace of the rule: a machine with no TPU
    takes the reference path, which has no `Lse`, and the program it builds
    may run on one that has."""
    Q, V = structs["Q"][0], structs["V"][0]
    B, H, T, _ = _shape_of(Q, _token_major(ctx))
    return {"Out": jax.ShapeDtypeStruct(Q.shape[:3] + V.shape[3:], Q.dtype),
            "Lse": jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32)}


@register_op("fused_attention", infer=_fused_attention_infer,
             propagate_seqlen=False, needs_rng=True)
def _fused_attention(ctx, Q, K, V, Kept=None):
    """Q, K: [B, H, T, D]; V: [B, H, T, Dv], the value heads' own width
    (latent attention: 192 over 128), `Dv == D` in the plain case; Out is
    [B, H, T, Dv]. Under `layout` "BTHD" all four are token-major, [B, T,
    H, D]: a projection's output under a free reshape, which the kernels
    read through their block specs (no transpose on either side). attrs:
    causal, sm_scale, dropout_rate, is_test, layout, and `window` (causal
    only): key j is visible to query i iff 0 <= i - j < window; the op
    tallies the score tiles its forward grid computes
    (`window_tiles_computed` on the compile event) and, as an op under a
    kept set does, those of them that run without the causal mask
    (`flash_tiles_unmasked`); a causal op without a window tallies the steps
    of its forward grid above the diagonal, for which nothing is fetched
    (`flash_dead_steps_held`); a causal op tallies the edge tiles its forward
    grid runs in strips and the sub-blocks of them it skips
    (`flash_edge_tiles_stripped`, `flash_subblocks_skipped`: 0 where a row
    is one K block, of token-major operands, off the alignment).
    `Kept` (optional): int8
    [B, T, T], the keys each query keeps of those below the diagonal, one
    set for all heads; no gradient, no dropout, not with a window; the
    `dsa_` kernels read its tiles beside the score tiles, and the op tallies
    `dsa_keys_kept` (by closed form: `min(t + 1, topk)` a row, `topk` its
    attribute) and `dsa_tiles_computed`.

    Replaces the reference's matmul+softmax+dropout+matmul composition
    (nets.py:329) with one O(T)-memory kernel. Dropout is applied to the
    attention weights inside the kernel, keyed from the executor's
    functional PRNG. On the kernel path the rule also returns `Lse`, the
    forward kernel's log-sum-exp (float32 [B*H, 1, T] in either layout),
    which the grad op reads back instead of running the forward kernel
    again."""
    sm_scale, causal, rate, window = _attrs(ctx, Q)
    token_major = _token_major(ctx)
    B, H, T, _ = _shape_of(Q, token_major)
    mesh = getattr(ctx.lowerer, "mesh", None) if ctx.lowerer else None
    if (mesh is not None and "sp" in mesh.axis_names
            and mesh.shape["sp"] > 1):
        if window is not None:
            raise NotImplementedError(
                f"a window ({window}) is not supported under sequence "
                f"parallelism: ring attention over the 'sp' mesh axis rotates "
                f"every K/V shard past every query shard and has no band; "
                f"run windowed layers without an 'sp' axis")
        # sequence parallelism: the ParallelExecutor shards the seq dim
        # over 'sp', so attention becomes Ring Attention — K/V shards
        # rotate over ICI while the online softmax accumulates.
        if rate:
            raise NotImplementedError(
                "attention-weight dropout is not supported under sequence "
                "parallelism; build the model with dropout_rate=0 (or move "
                "dropout outside the attention op)")
        if T % mesh.shape["sp"] != 0:
            raise ValueError(
                f"sequence length {T} is not divisible by the "
                f"{mesh.shape['sp']}-way 'sp' mesh axis; pad the sequence "
                f"or choose an sp that divides it")
        if V.shape[-1] != Q.shape[-1]:
            raise NotImplementedError(
                f"ring attention under the 'sp' mesh axis carries one head "
                f"width; got query/key heads of {Q.shape[-1]} and value "
                f"heads of {V.shape[-1]}")
        if token_major:     # the ring is written over [B, H, T/sp, D] shards
            Q, K, V = (x.transpose(0, 2, 1, 3) for x in (Q, K, V))
        out = ring_attention(Q, K, V, mesh, axis="sp", causal=causal,
                             sm_scale=sm_scale)
        return {"Out": out.transpose(0, 2, 1, 3) if token_major else out}
    seed = _dropout_seed(ctx, rate)
    # the tallies are the forward grid's: not its grad op's trace
    forward_op = ctx.op is not None and ctx.op.type == "fused_attention"
    if forward_op and _interior_apart(window, Kept):
        ctx.tally("flash_tiles_unmasked", B * H * interior_tiles(T, window))
    if forward_op and causal and _window_of(window, T) is None \
            and T % _LANES == 0:
        ctx.tally("flash_dead_steps_held",
                  B * H * _dead_steps(T, *_blk(T, True)))
    if forward_op and causal:
        tiles, skipped = (0, 0) if token_major else edge_strips(T, window)
        ctx.tally("flash_edge_tiles_stripped", B * H * tiles)
        ctx.tally("flash_subblocks_skipped", B * H * skipped)
    if Kept is not None:
        if rate:
            raise NotImplementedError(
                "attention-weight dropout is not supported under a kept set")
        _check_kept(Kept, Q, causal, window, token_major)
        if forward_op:
            ctx.tally("dsa_keys_kept",
                      B * kept_pairs(T, ctx.attr("topk", T)))
            ctx.tally("dsa_tiles_computed", B * H * causal_tiles(T))
        if _pallas_ok(Q, rate, V):
            out, lse = _dsa_out_lse(Q, K, V, Kept, sm_scale)
            return {"Out": out, "Lse": lse}
        return {"Out": _reference(Q, K, V, causal, sm_scale, rate, seed,
                                  window, token_major, Kept)}
    if window is not None and forward_op:
        ctx.tally("window_tiles_computed", B * H * window_tiles(T, window))
    if _pallas_ok(Q, rate, V, window, token_major):
        out, lse = _flash_out_lse(Q, K, V, seed, causal, sm_scale, rate,
                                  window, token_major)
        return {"Out": out, "Lse": lse}
    return {"Out": _reference(Q, K, V, causal, sm_scale, rate, seed, window,
                              token_major)}


@register_grad("fused_attention")
def _fused_attention_grad(ctx, ins, out_grads):
    """dQ/dK/dV from the backward kernel alone, on the forward op's saved
    `Out` and `Lse`, in the operands' layout. Which path runs is read off
    the environment: where the
    forward op left no `Lse` (a program built without the slot, the ring
    path, the CPU reference path) the forward rule is traced again under
    `jax.vjp`, as the generic grad lowering does for every op without a
    grad rule. A grad rule sees the scope's values, so AMP's casts
    (registry.amp_cast: float32 -> bf16 for this op) and the cast of
    `Out@GRAD` to the primal's dtype happen here."""
    g = out_grads["Out"][0]
    if g is None:
        return {}
    opdef = get_op_def("fused_attention")
    slots = ("Q", "K", "V")
    raw = [ins[s][0] for s in slots]
    kept = ins.get("Kept", [None])[0]       # carries no gradient
    rest = {} if kept is None else {"Kept": [kept]}
    out, lse = ctx.fwd_outs["Out"][0], ctx.fwd_outs.get("Lse", [None])[0]
    if lse is None:
        out, vjp = jax.vjp(
            lambda q, k, v: call_rule(
                opdef, ctx, {"Q": [q], "K": [k], "V": [v], **rest})["Out"][0],
            *raw)
        grads = vjp(g.astype(out.dtype))
    else:
        cast = amp_cast(opdef, ctx, {s: [x] for s, x in zip(slots, raw)})
        q, k, v = (cast[s][0] for s in slots)
        sm_scale, causal, rate, window = _attrs(ctx, q)
        grads = _flash_backward(q, k, v, out, lse, g.astype(out.dtype),
                                causal, sm_scale, rate,
                                _dropout_seed(ctx, rate), window,
                                _token_major(ctx), kept)
    return {s: d.astype(x.dtype) for s, d, x in zip(slots, grads, raw)}


# ---------------------------------------------------------------------------
# ring attention: sequence parallelism over an 'sp' mesh axis
# ---------------------------------------------------------------------------

def ring_attention(q, k, v, mesh, axis="sp", causal=False, sm_scale=None):
    """Exact attention with Q/K/V sequence-sharded over `axis`.

    Each device holds a [B, H, T/sp, D] shard; K/V shards rotate around the
    ring via ppermute while a running online-softmax (m, l, acc) accumulates
    — the Ring Attention algorithm. Communication rides ICI neighbor links;
    peak memory per chip is O(T/sp). Built from differentiable jax ops
    (ppermute has a transpose rule), so training works through it.

    Exceeds reference capability: the reference has no sequence parallelism
    (SURVEY.md §5.7).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    sp = mesh.shape[axis]

    def local(qs, ks, vs):
        idx = lax.axis_index(axis)
        Tl = qs.shape[2]

        def block(carry, chunk_i):
            m, l, acc, kc, vc = carry
            # which global chunk do we currently hold?
            src = (idx - chunk_i) % sp
            s = jnp.einsum("bhqd,bhkd->bhqk", qs, kc).astype(jnp.float32) \
                * sm_scale
            if causal:
                row = (idx * Tl + jnp.arange(Tl))[:, None]
                col = (src * Tl + jnp.arange(Tl))[None, :]
                s = jnp.where(col[None, None] > row[None, None], NEG_INF, s)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vc.dtype), vc).astype(jnp.float32)
            perm = [(i, (i + 1) % sp) for i in range(sp)]
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
            return (m_new, l_new, acc_new, kc, vc), None

        B, H, _, D = qs.shape
        m0 = jnp.full((B, H, Tl), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, Tl), jnp.float32)
        acc0 = jnp.zeros((B, H, Tl, D), jnp.float32)
        (m, l, acc, _, _), _ = lax.scan(block, (m0, l0, acc0, ks, vs),
                                        jnp.arange(sp))
        return (acc / jnp.maximum(l, 1e-20)[..., None]).astype(qs.dtype)

    # carry the mesh's OTHER axes in the specs too: naming only 'sp' would
    # make GSPMD all-gather the full batch/head dims into every dp/mp
    # group and compute attention redundantly across them
    names = mesh.axis_names
    b_ax = "dp" if ("dp" in names and q.shape[0] % mesh.shape["dp"] == 0) \
        else None
    h_ax = "mp" if ("mp" in names and q.shape[1] % mesh.shape["mp"] == 0) \
        else None
    spec = P(b_ax, h_ax, axis, None)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
