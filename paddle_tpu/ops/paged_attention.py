"""fluid-decode: ragged paged attention over a block-allocated KV cache.

Autoregressive decode is memory-bound: every generated token re-reads the
whole K/V history. Keeping that history contiguous per sequence would
force either per-length compile signatures (a recompile per token) or a
[slots, max_context] dense cache whose padding is re-read every step.
The paged layout (Ragged Paged Attention, PAPERS.md) fixes both at once:

- K/V live in fixed-size BLOCKS ``[num_blocks, block_size, heads, dh]``
  owned by a persistent scope var, so every decode step has ONE static
  shape signature and the compile cache stays warm forever;
- each sequence owns an ordered list of block ids (its BLOCK TABLE, fed
  as a ``[slots, max_blocks_per_seq]`` int32 array); attention gathers
  K/V through the table and masks lanes at or past the sequence length,
  so wildly ragged sequences share one step;
- block 0 is a reserved TRASH block: inactive slots (and the padding
  lanes of prefill writes) scatter there, keeping every scatter static —
  no lane is ever conditionally skipped, just redirected somewhere no
  read can see (reads mask by position, and position >= seq_len lanes
  are masked regardless of which block the table names).

Two phases share the cache:

- ``prefill_attention``: the prompt runs ordinary causal (flash)
  attention at its bucket-ladder rung, and its per-position K/V are
  scattered into the sequence's blocks in the same jitted step;
- ``paged_attention``: one new token per occupied slot — append its K/V
  at position ``seq_len - 1``, attend over ``[0, seq_len)`` through the
  block table.

On TPU (or, on CPU, under PADDLE_TPU_PALLAS_INTERPRET=1) the decode read
side runs as a Pallas kernel streaming cache blocks through the grid's
innermost dimension with the block-table indirection in the index map
(scalar prefetch); on a CPU backend without the switch a masked-lane jnp
reference computes the same math — tests pin the reference path
bit-identical to dense attention on the valid region, and the kernel
against the reference under the interpreter. On the TPU there is no
second path: a cache geometry outside the kernel's envelope raises.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.registry import register_op
from . import _kernels
from .pallas_attention import NEG_INF, flash_attention

_LANES = 128


def _pallas_ok(q, k_cache):
    """Kernel or reference? The reference is a CPU-only path. On the TPU
    the kernel's blocks must be whole tiles — head_dim a multiple of the
    128 lanes, block_size * heads a multiple of the cache dtype's sublane
    tile (8 for float32, 32 for int8) — and anything else raises."""
    if not _kernels.on_chip():
        return _kernels.interpret()
    _, H, Dh = q.shape
    rows = k_cache.shape[1] * H
    sublanes = 32 // k_cache.dtype.itemsize
    if Dh % _LANES or rows % sublanes:
        raise ValueError(
            f"paged attention on the {jax.default_backend()!r} backend "
            f"needs head_dim % {_LANES} == 0 and block_size * heads % "
            f"{sublanes} == 0 for a {k_cache.dtype} cache; got head_dim "
            f"{Dh}, block_size {k_cache.shape[1]}, heads {H}")
    return True


# ---------------------------------------------------------------------------
# cache scatter (append / prefill write)
# ---------------------------------------------------------------------------

def kv_cache_append(k_cache, v_cache, k_new, v_new, block_tables, seq_lens):
    """Write one new token's K/V per slot at position ``seq_len - 1``.

    ``k_new``/``v_new``: [S, H, Dh]; caches [NB, BS, H, Dh]. Inactive
    slots (seq_len == 0) write into the trash block 0 — the scatter stays
    static and nothing ever reads block 0 unmasked."""
    bs = k_cache.shape[1]
    pos = jnp.maximum(seq_lens - 1, 0)
    blk = jnp.take_along_axis(block_tables, (pos // bs)[:, None],
                              axis=1)[:, 0]
    active = seq_lens > 0
    blk = jnp.where(active, blk, 0)
    off = jnp.where(active, pos % bs, 0)
    k_cache = k_cache.at[blk, off].set(k_new.astype(k_cache.dtype))
    v_cache = v_cache.at[blk, off].set(v_new.astype(v_cache.dtype))
    return k_cache, v_cache


def kv_cache_prefill_write(k_cache, v_cache, k, v, block_tables, seq_lens):
    """Scatter a padded prompt's K/V ([B, T, H, Dh]) into each row's
    blocks; positions at or past the row's seq_len land in trash block 0."""
    bs = k_cache.shape[1]
    B, T = k.shape[0], k.shape[1]
    t = jnp.arange(T)
    blk = jnp.take_along_axis(
        block_tables, jnp.broadcast_to((t // bs)[None, :], (B, T)), axis=1)
    valid = t[None, :] < seq_lens[:, None]
    blk = jnp.where(valid, blk, 0)
    off = jnp.broadcast_to((t % bs)[None, :], (B, T))
    flat_blk = blk.reshape(-1)
    flat_off = off.reshape(-1)
    k_cache = k_cache.at[flat_blk, flat_off].set(
        k.reshape((B * T,) + k.shape[2:]).astype(k_cache.dtype))
    v_cache = v_cache.at[flat_blk, flat_off].set(
        v.reshape((B * T,) + v.shape[2:]).astype(v_cache.dtype))
    return k_cache, v_cache


# ---------------------------------------------------------------------------
# masked-lane reference math (CPU path; the numerical contract)
# ---------------------------------------------------------------------------

def paged_attention_reference(q, k_cache, v_cache, block_tables, seq_lens,
                              sm_scale):
    """q: [S, H, Dh] (one token per slot). Gathers each slot's K/V
    through its block table into a dense [S, T, H, Dh] view (T =
    max_blocks_per_seq * block_size), masks lanes >= seq_len, and runs
    one softmax(QK^T)V. Inactive slots return zeros."""
    S, H, Dh = q.shape
    nb, bs = k_cache.shape[0], k_cache.shape[1]
    T = block_tables.shape[1] * bs
    flat = (block_tables[:, :, None] * bs
            + jnp.arange(bs)[None, None, :]).reshape(S, T)
    k = jnp.take(k_cache.reshape(nb * bs, H, Dh), flat, axis=0)
    v = jnp.take(v_cache.reshape(nb * bs, H, Dh), flat, axis=0)
    s = jnp.einsum("shd,sthd->sht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    mask = jnp.arange(T)[None, :] < seq_lens[:, None]
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("sht,sthd->shd", p, v.astype(jnp.float32)) \
        / jnp.maximum(l, 1e-20)[..., 0][..., None]
    o = jnp.where((seq_lens > 0)[:, None, None], o, 0.0)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# pallas kernel: stream cache blocks via block-table indirection
# ---------------------------------------------------------------------------

def _paged_decode_kernel(*refs, sm_scale, block_size, quantized):
    """Grid (slot, block-ordinal). The k/v BlockSpec index maps read the
    prefetched block table, so program (s, j) sees the j-th cache block
    of slot s — the paged gather costs a scalar lookup, not a host-side
    reorder. Online-softmax state is carried in VMEM scratch across the
    innermost (sequential) dimension, exactly the flash-attention idiom
    of ops/pallas_attention.py.

    The cache block arrives as a 2-D [block_size * H, Dh] tile (row
    b * H + h), so both contractions are plain 2-D MXU matmuls: q @ K^T
    gives [H, block_size * H] scores of every query head against every
    (position, head) row, and the mask keeps only each head's own rows
    (and positions inside the sequence). The H-fold surplus of FLOPs is
    free — a decode step is bound by reading K/V, not by the MXU — and
    nothing needs a batched dot or an in-kernel transpose.

    `quantized`: the blocks are int8 and two more scalar-prefetch
    operands carry the per-block K/V scales, applied right after the
    load."""
    from jax.experimental import pallas as pl

    if quantized:
        (seq_lens_ref, bt_ref, ks_ref, vs_ref, head_ref, pos_ref, q_ref,
         k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc) = refs
    else:
        (seq_lens_ref, bt_ref, head_ref, pos_ref, q_ref,
         k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc) = refs

    s = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    seq_len = seq_lens_ref[s]
    # blocks wholly past the sequence contribute nothing; an inactive
    # slot (seq_len 0) never updates, leaving acc at zeros
    live = j * block_size < seq_len

    @pl.when(live)
    def _update():
        q = q_ref[0].astype(jnp.float32)                # [H, Dh]
        k = k_ref[0].astype(jnp.float32)                # [BS * H, Dh]
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            blk = bt_ref[s, j]
            k = k * ks_ref[blk]
            v = v * vs_ref[blk]
        scores = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [H, BS * H]
        row = lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        keep = (head_ref[...] == row) \
            & (j * block_size + pos_ref[...] < seq_len)
        scores = jnp.where(keep, scores, NEG_INF)
        # m/l live lane-broadcast in [H, 128] scratch; [:, :1] reads the
        # per-head column back
        m = m_sc[...]
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        # an unkept entry must weigh exactly 0 (not exp(NEG_INF - m),
        # which is 1 while a head's running max is still NEG_INF)
        p = jnp.where(keep, jnp.exp(scores - m_new[:, :1]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha[:, :1] + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        l = jnp.maximum(l_sc[...], 1e-20)
        o_ref[0] = (acc_sc[...] / l[:, :1]).astype(o_ref.dtype)


def _paged_call(q, k_cache, v_cache, block_tables, seq_lens, sm_scale,
                scales=()):
    """Shared pallas_call of the float and int8 decode reads. `scales`:
    () or (k_scale, v_scale), each [num_blocks] float32 riding SMEM next
    to the block table."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, Dh = q.shape
    nblk, bs = k_cache.shape[0], k_cache.shape[1]
    rows = bs * H
    max_b = block_tables.shape[1]
    n_pre = 2 + len(scales)
    kernel = functools.partial(_paged_decode_kernel, sm_scale=sm_scale,
                               block_size=bs, quantized=bool(scales))
    # which head / which in-block position each row of the 2-D cache
    # tile belongs to (constants; fetched once, their block never moves)
    col = np.arange(rows, dtype=np.int32)[None, :]
    head_of_row, pos_of_row = col % H, col // H
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre,
        grid=(S, max_b),
        in_specs=[
            pl.BlockSpec((1, rows), lambda s, j, *pre: (0, 0)),
            pl.BlockSpec((1, rows), lambda s, j, *pre: (0, 0)),
            pl.BlockSpec((1, H, Dh), lambda s, j, *pre: (s, 0, 0)),
            pl.BlockSpec((1, rows, Dh),
                         lambda s, j, sl, bt, *pre: (bt[s, j], 0, 0)),
            pl.BlockSpec((1, rows, Dh),
                         lambda s, j, sl, bt, *pre: (bt[s, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, Dh), lambda s, j, *pre: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, Dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_kernels.interpret(),
        name="paged_attention",
    )(seq_lens.astype(jnp.int32), block_tables.astype(jnp.int32),
      *(sc.astype(jnp.float32) for sc in scales),
      head_of_row, pos_of_row, q,
      k_cache.reshape(nblk, rows, Dh), v_cache.reshape(nblk, rows, Dh))


def _paged_attention_pallas(q, k_cache, v_cache, block_tables, seq_lens,
                            sm_scale):
    return _paged_call(q, k_cache, v_cache, block_tables, seq_lens,
                       sm_scale)


def paged_attention(q, k_cache, v_cache, block_tables, seq_lens,
                    sm_scale=None):
    """Public entry: kernel on TPU / under the interpreter, masked-lane
    reference math on a plain CPU backend (the CPU test suite pins the
    reference bit-identical to dense attention on the valid region)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if _pallas_ok(q, k_cache):
        return _paged_attention_pallas(q, k_cache, v_cache, block_tables,
                                       seq_lens, sm_scale)
    return paged_attention_reference(q, k_cache, v_cache, block_tables,
                                     seq_lens, sm_scale)


# ---------------------------------------------------------------------------
# registered ops (the decode/prefill program building blocks)
# ---------------------------------------------------------------------------

def _no_window(ctx):
    """The paged kernels attend over every block of a sequence's table."""
    if ctx.attr("window") is not None:
        raise NotImplementedError(
            f"a window ({ctx.attr('window')}) is not supported by the paged "
            f"attention ops: the decode kernel walks a sequence's whole block "
            f"table and the cache frees no block that has left the window; "
            f"windowed layers train through fused_attention only")


@register_op("paged_attention", propagate_seqlen=False)
def _paged_attention_op(ctx, Q, K, V, KCache, VCache, BlockTables, SeqLens):
    """One decode step. Q/K/V: [slots, d_model] — this step's token per
    slot. Appends K/V at position seq_len-1 (in place: KCacheOut/VCacheOut
    alias the cache vars, so the executor donates the HBM buffers), then
    attends over [0, seq_len) through the block table. attrs: num_heads,
    sm_scale."""
    _no_window(ctx)
    H = int(ctx.attr("num_heads", 1))
    S, D = Q.shape
    Dh = D // H
    sm_scale = float(ctx.attr("sm_scale", 1.0 / math.sqrt(Dh)))
    seq = SeqLens.astype(jnp.int32)
    bt = BlockTables.astype(jnp.int32)
    kc, vc = kv_cache_append(KCache, VCache, K.reshape(S, H, Dh),
                             V.reshape(S, H, Dh), bt, seq)
    out = paged_attention(Q.reshape(S, H, Dh), kc, vc, bt, seq, sm_scale)
    return {"Out": out.reshape(S, D), "KCacheOut": kc, "VCacheOut": vc}


@register_op("prefill_attention", propagate_seqlen=False)
def _prefill_attention_op(ctx, Q, K, V, KCache, VCache, BlockTables,
                          SeqLens):
    """Prompt phase. Q/K/V: [rows, T, d_model] at a bucket-ladder rung.
    Runs causal attention over the padded prompt (right-padding is
    invisible to valid positions under the causal mask) and scatters each
    row's K/V into its blocks in the same step. attrs: num_heads,
    sm_scale."""
    _no_window(ctx)
    H = int(ctx.attr("num_heads", 1))
    B, T, D = Q.shape
    Dh = D // H
    sm_scale = float(ctx.attr("sm_scale", 1.0 / math.sqrt(Dh)))
    seq = SeqLens.astype(jnp.int32)
    bt = BlockTables.astype(jnp.int32)
    k4 = K.reshape(B, T, H, Dh)
    v4 = V.reshape(B, T, H, Dh)
    out = flash_attention(
        Q.reshape(B, T, H, Dh).transpose(0, 2, 1, 3),
        k4.transpose(0, 2, 1, 3), v4.transpose(0, 2, 1, 3),
        jnp.int32(0), True, sm_scale, 0.0)
    kc, vc = kv_cache_prefill_write(KCache, VCache, k4, v4, bt, seq)
    return {"Out": out.transpose(0, 2, 1, 3).reshape(B, T, D),
            "KCacheOut": kc, "VCacheOut": vc}


@register_op("gather_last_token", propagate_seqlen=False)
def _gather_last_token(ctx, X, SeqLens):
    """X: [rows, T, D] -> Out: [rows, D], each row's position
    seq_len - 1 (clamped into range; rows with seq_len 0 read position 0
    — callers never use their output)."""
    idx = jnp.clip(SeqLens.astype(jnp.int32) - 1, 0, X.shape[1] - 1)
    return {"Out": jnp.take_along_axis(
        X, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]}


# ---------------------------------------------------------------------------
# fluid-torrent: int8-quantized KV residency (per-BLOCK abs-max scale)
# ---------------------------------------------------------------------------
# The cache arrays become int8 [NB, BS, H, Dh] with one float32 scale
# per block ([NB], separate K and V scales): value = int8 * scale[block].
# Same symmetric +-127 bins as the wire codec (EQuARX idiom), but the
# quantization GROUP is the residency unit — a block — so a block's
# scale travels with it over the wire and a decode replica can admit a
# streamed block without requantizing.
#
# Invariants:
# - prefill OWNS its blocks: the write SETS each written block's scale
#   to its group abs-max/127 (a recycled block's stale scale is
#   overwritten, never consulted);
# - decode append GROWS a block: the first token written into a block
#   sets its scale fresh; a later token may RAISE it (never lower —
#   already-quantized neighbors would lose range), in which case the
#   block's resident int8 values are requantized by old/new and the
#   event is counted (RequantCountOut — the serve engine meters it as
#   serve_kv_requant_events_total; frequent requants mean the rounding
#   error budget is being spent, see docs/TORRENT.md);
# - attention DEQUANTIZES at the gather: Q and the in-flight K/V stay
#   float32 (prefill's own attention runs on the exact fp K/V — only
#   RESIDENCY is quantized), so the first generated token is exact and
#   quantization error enters through decode-step history reads only.

_Q8_BINS = 127.0


def _q8_append_one(cache, scale, new, block_tables, seq_lens):
    """Append one token's values per slot into an int8 cache.
    `new`: [S, H, Dh] float32. Returns (cache, scale, n_requant)."""
    bs = cache.shape[1]
    pos = jnp.maximum(seq_lens - 1, 0)
    blk = jnp.take_along_axis(block_tables, (pos // bs)[:, None],
                              axis=1)[:, 0]
    active = seq_lens > 0
    blk = jnp.where(active, blk, 0)
    off = jnp.where(active, pos % bs, 0)
    first = (pos % bs) == 0            # first token written into the block
    tok = new.astype(jnp.float32)
    needed = jnp.max(jnp.abs(tok), axis=(1, 2)) / _Q8_BINS        # [S]
    old = scale[blk]                                              # [S]
    base = jnp.where(first, jnp.float32(0.0), old)
    s_new = jnp.maximum(base, needed)
    requant = active & (~first) & (needed > old)
    # requantize the whole resident block where its scale grew; ratio 1
    # elsewhere makes the rewrite an exact identity (and the conflicting
    # inactive-slot writes all target trash block 0 with ratio 1)
    ratio = jnp.where(requant, old / jnp.maximum(s_new, 1e-30),
                      jnp.float32(1.0))
    adj = jnp.rint(cache[blk].astype(jnp.float32)
                   * ratio[:, None, None, None])
    cache = cache.at[blk].set(adj.astype(cache.dtype))
    safe = jnp.where(s_new > 0, s_new, jnp.float32(1.0))
    q = jnp.rint(jnp.clip(tok / safe[:, None, None], -_Q8_BINS, _Q8_BINS))
    cache = cache.at[blk, off].set(q.astype(cache.dtype))
    scale = scale.at[blk].set(jnp.where(active, s_new, old))
    return cache, scale, jnp.sum(requant.astype(jnp.int32))


def _q8_prefill_write_one(cache, scale, x, block_tables, seq_lens):
    """Scatter a padded prompt's values ([B, T, H, Dh]) into an int8
    cache, setting each written block's scale to its group abs-max."""
    bs = cache.shape[1]
    B, T = x.shape[0], x.shape[1]
    n_ord = -(-T // bs)
    t = jnp.arange(T)
    valid = t[None, :] < seq_lens[:, None]                        # [B, T]
    blk = jnp.take_along_axis(
        block_tables, jnp.broadcast_to((t // bs)[None, :], (B, T)), axis=1)
    blk = jnp.where(valid, blk, 0)
    off = jnp.broadcast_to((t % bs)[None, :], (B, T))
    xm = jnp.where(valid[:, :, None, None], x.astype(jnp.float32), 0.0)
    pad = n_ord * bs - T
    xp = jnp.pad(xm, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else xm
    grp = xp.reshape(B, n_ord, bs, x.shape[2], x.shape[3])
    needed = jnp.max(jnp.abs(grp), axis=(2, 3, 4)) / _Q8_BINS  # [B, n_ord]
    safe = jnp.where(needed > 0, needed, jnp.float32(1.0))
    per_pos = jnp.repeat(safe, bs, axis=1)[:, :T]                 # [B, T]
    q = jnp.rint(jnp.clip(xm / per_pos[:, :, None, None],
                          -_Q8_BINS, _Q8_BINS))
    cache = cache.at[blk.reshape(-1), off.reshape(-1)].set(
        q.reshape((B * T,) + x.shape[2:]).astype(cache.dtype))
    # overwrite the scale of every block that received a valid position
    # (prefill owns the block); rows/ordinals past seq_len redirect to
    # trash block 0 where they rewrite its existing scale
    has = (jnp.arange(n_ord)[None, :] * bs) < seq_lens[:, None]
    blk_sc = jnp.where(has, block_tables[:, :n_ord], 0)
    scale = scale.at[blk_sc.reshape(-1)].set(
        jnp.where(has, needed, scale[blk_sc]).reshape(-1))
    return cache, scale


def paged_attention_q8_reference(q, k_cache, v_cache, k_scale, v_scale,
                                 block_tables, seq_lens, sm_scale):
    """Reference math of the quantized decode read: gather int8 blocks
    through the table, dequantize by per-block scale, then the same
    masked softmax as paged_attention_reference."""
    S, H, Dh = q.shape
    nb, bs = k_cache.shape[0], k_cache.shape[1]
    T = block_tables.shape[1] * bs
    flat = (block_tables[:, :, None] * bs
            + jnp.arange(bs)[None, None, :]).reshape(S, T)
    ks = jnp.repeat(k_scale[block_tables], bs, axis=1)            # [S, T]
    vs = jnp.repeat(v_scale[block_tables], bs, axis=1)
    k = jnp.take(k_cache.reshape(nb * bs, H, Dh), flat,
                 axis=0).astype(jnp.float32) * ks[:, :, None, None]
    v = jnp.take(v_cache.reshape(nb * bs, H, Dh), flat,
                 axis=0).astype(jnp.float32) * vs[:, :, None, None]
    s = jnp.einsum("shd,sthd->sht", q.astype(jnp.float32), k) * sm_scale
    mask = jnp.arange(T)[None, :] < seq_lens[:, None]
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("sht,sthd->shd", p, v) \
        / jnp.maximum(l, 1e-20)[..., 0][..., None]
    o = jnp.where((seq_lens > 0)[:, None, None], o, 0.0)
    return o.astype(q.dtype)


def _paged_attention_q8_pallas(q, k_cache, v_cache, k_scale, v_scale,
                               block_tables, seq_lens, sm_scale):
    return _paged_call(q, k_cache, v_cache, block_tables, seq_lens,
                       sm_scale, scales=(k_scale, v_scale))


def paged_attention_q8(q, k_cache, v_cache, k_scale, v_scale, block_tables,
                       seq_lens, sm_scale=None):
    """Quantized-residency decode read: kernel on TPU / under the
    interpreter, dequantizing reference math on a plain CPU backend."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if _pallas_ok(q, k_cache):
        return _paged_attention_q8_pallas(q, k_cache, v_cache, k_scale,
                                          v_scale, block_tables, seq_lens,
                                          sm_scale)
    return paged_attention_q8_reference(q, k_cache, v_cache, k_scale,
                                        v_scale, block_tables, seq_lens,
                                        sm_scale)


@register_op("paged_attention_q8", propagate_seqlen=False)
def _paged_attention_q8_op(ctx, Q, K, V, KCache, VCache, KScale, VScale,
                           RequantCount, BlockTables, SeqLens):
    """One decode step over int8 caches. Same contract as
    paged_attention plus per-block scale vars ([num_blocks] f32, updated
    in place alongside their cache) and a [1] int32 requant-event
    counter the serve engine meters."""
    _no_window(ctx)
    H = int(ctx.attr("num_heads", 1))
    S, D = Q.shape
    Dh = D // H
    sm_scale = float(ctx.attr("sm_scale", 1.0 / math.sqrt(Dh)))
    seq = SeqLens.astype(jnp.int32)
    bt = BlockTables.astype(jnp.int32)
    kc, ks, n_k = _q8_append_one(KCache, KScale, K.reshape(S, H, Dh),
                                 bt, seq)
    vc, vs, n_v = _q8_append_one(VCache, VScale, V.reshape(S, H, Dh),
                                 bt, seq)
    out = paged_attention_q8(Q.reshape(S, H, Dh), kc, vc, ks, vs, bt, seq,
                             sm_scale)
    return {"Out": out.reshape(S, D), "KCacheOut": kc, "VCacheOut": vc,
            "KScaleOut": ks, "VScaleOut": vs,
            "RequantCountOut": RequantCount + (n_k + n_v)}


@register_op("prefill_attention_q8", propagate_seqlen=False)
def _prefill_attention_q8_op(ctx, Q, K, V, KCache, VCache, KScale, VScale,
                             BlockTables, SeqLens):
    """Prompt phase over int8 caches: attention runs on the exact fp
    K/V in flight (prefill logits — and therefore the first token — are
    bit-identical to the fp cache), quantization happens only at the
    residency write. No requant counter: prefill always owns the blocks
    it writes."""
    _no_window(ctx)
    H = int(ctx.attr("num_heads", 1))
    B, T, D = Q.shape
    Dh = D // H
    sm_scale = float(ctx.attr("sm_scale", 1.0 / math.sqrt(Dh)))
    seq = SeqLens.astype(jnp.int32)
    bt = BlockTables.astype(jnp.int32)
    k4 = K.reshape(B, T, H, Dh)
    v4 = V.reshape(B, T, H, Dh)
    out = flash_attention(
        Q.reshape(B, T, H, Dh).transpose(0, 2, 1, 3),
        k4.transpose(0, 2, 1, 3), v4.transpose(0, 2, 1, 3),
        jnp.int32(0), True, sm_scale, 0.0)
    kc, ks = _q8_prefill_write_one(KCache, KScale, k4, bt, seq)
    vc, vs = _q8_prefill_write_one(VCache, VScale, v4, bt, seq)
    return {"Out": out.transpose(0, 2, 1, 3).reshape(B, T, D),
            "KCacheOut": kc, "VCacheOut": vc,
            "KScaleOut": ks, "VScaleOut": vs}
