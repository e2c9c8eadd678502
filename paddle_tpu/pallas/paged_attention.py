"""Decode attention over a paged K/V pool, in Pallas, for TPU: each
lane reads its LIVE pages straight out of the pool through its page
table and nothing else (the op is `paged_attention`,
ops/attention_ops.py; the reference lowering there gathers the whole
window and masks).

One query row per lane: q [S, H, dh], pools [N, pt, KVH, dh] (the
layout kv_page_write/append keep in the step programs, and
kv_page_cow wherever a forked page is copied: inside a prefill chunk's
program, in front of a decode step's), table [S, P] int32, positions
[S] int32. Lane s attends to logical positions 0..positions[s]. KVH
divides H: query head h reads K/V head h // (H / KVH); where the two
counts are equal every head has its own. With a `window` (a sliding
layer's; 0: none) lane s attends to max(0, positions[s] - window +
1)..positions[s]: its walk starts at the page that holds the first of
them, the pages before it are never copied, and the first page is
masked to the window's edge as the last is to the position. Such a call
carries the name `paged_window_attention` in the device's trace.

The pools stay in HBM; table and positions are scalar-prefetched. The
grid is one step per lane, and a lane walks ceil((pos + 1) / pt) pages
in blocks of `_BLOCK_PAGES`, each page one contiguous DMA (all heads
together: pt * H * dh floats) into a double-buffered VMEM block. The
block after the one being worked on is always in flight, across lanes
too: a lane's last block starts the next lane's first. Pages past a
lane's last are never copied.

The heads stay together in a block, so a block is the [C, dh] matrix of
C = pages * pt * KVH (token, K/V head) rows and both contractions run
on the MXU over all of it: scores [H, C] = q . block^T, of which row h
keeps the columns of its own K/V head (the others are masked like the
dead tail of the last page) and an online softmax in fp32 (m, l, acc
[H, dh]) folds the blocks. The H / KVH query heads of one K/V head are
rows of the one product: a page is read once, not once a query head.
That is KVH times the multiplies the sum needs, on a unit that has
nothing else to do in a decode step; what the kernel waits for is the
pages. The contractions run at Mosaic's default
precision for float32 operands, one bfloat16 pass like every other
matmul of the float32 serving path (1.7e-3 of the result on a v5e;
Precision.HIGHEST reads 1.5e-7 and doubles the kernel's time where few
lanes are live, because a block is multiplied whole: PERF.md, PR 25).

A masked column contributes exp(-1e30 - m) = 0.0 times whatever the
buffer holds there, so the V buffer is zeroed once (a page slot that
was never copied into must not hold a NaN); K's never reaches the
result unselected.

`paged_latent_attention` is the same walk for a pool of latent rows
(multi-head latent attention in its absorbed form,
ops/latent_attention_ops.py): pool [N, pt, row], ONE row a token for
all heads, q [S, H, row]. It is the case KVH = 1 of the above with the
values taken from the keys: scores [H, C] = q . block^T over the whole
row and the second product reads the same block's first `value_dim`
columns, so a page is copied once for both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['paged_attention', 'supported', 'paged_latent_attention',
           'latent_supported', 'paged_attention_d64']

_NEG_INF = -1e30
# pages a block holds: 8 pages of 16 tokens x 16 heads x 128 floats are
# 1 MB of K and 1 MB of V a block (2.5 us of HBM time, several times a
# loop step's fixed cost), 4 MB of VMEM double-buffered. Measured on a
# v5e at the 1.3B serving shapes (PERF.md, PR 25): 2 / 4 / 8 pages read
# a full pool at 559 / 661 / 725 GB/s, 16 no faster than 8 and slower
# where few lanes are live (a block is worked on whole, however few of
# its pages are).
_BLOCK_PAGES = 8
# ... and no fewer than these bytes: a page of 2 K/V heads is 16 KB, and
# 8 of them a block would be a loop step of fixed cost for 0.3 us of
# HBM time. (A 16-head page is 128 KB: 8 pages either way.)
_BLOCK_BYTES = 1 << 19


def block_pages(pages_per_slot, page_tokens, kv_heads, head_dim, itemsize=4):
    page = page_tokens * kv_heads * head_dim * itemsize
    return min(pages_per_slot, max(_BLOCK_PAGES, _BLOCK_BYTES // page))


def supported(page_tokens, head_dim):
    """Shapes the kernel tiles: a (token, head) row is a whole number
    of lanes (or half a row: paged_attention_d64) and a page tiles."""
    return head_dim % 128 in (0, 64) and page_tokens % 8 == 0


def _kernel(table_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, slot_ref, *, sm_scale, pt, heads, kv_heads,
            bp, pages_per_slot, lanes, window=0):
    s = pl.program_id(0)
    cols = bp * pt * kv_heads
    rep = heads // kv_heads

    def first_page(lane):
        """The page a lane's walk starts at: the one that holds the
        first position of its window."""
        return jnp.maximum(pos_ref[lane] - (window - 1), 0) // pt

    def n_pages(lane):
        last = jnp.minimum(pos_ref[lane] // pt + 1, pages_per_slot)
        return last - first_page(lane) if window else last

    def copies(lane, blk, slot, wait=False):
        """Start (or wait for) the copies of block `blk` of `lane`, its
        live pages only, into buffer `slot`."""
        live = n_pages(lane)
        for j in range(bp):
            g = blk * bp + j

            @pl.when(g < live)
            def _():
                at = g + first_page(lane) if window else g
                page = table_ref[lane * pages_per_slot + at]
                for hbm, buf, which in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                    dma = pltpu.make_async_copy(
                        hbm.at[page], buf.at[slot, j], sems.at[which, slot])
                    dma.wait() if wait else dma.start()

    @pl.when(s == 0)
    def _():
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        copies(0, 0, 0)

    slot0 = slot_ref[0]
    pos = pos_ref[s]
    n_blk = pl.cdiv(n_pages(s), bp)
    q = q_ref[...].astype(jnp.float32) * sm_scale            # [H, dh]
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 1)
    tok = col // kv_heads                   # token of a column, in block
    kv_head = col - tok * kv_heads
    row = jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 0)
    own = kv_head == (row if rep == 1 else row // rep)

    def block(i, carry):
        m, l, acc = carry
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blk)
        def _():
            copies(s, i + 1, 1 - slot)

        @pl.when(jnp.logical_and(i + 1 == n_blk, s + 1 < lanes))
        def _():
            copies(s + 1, 0, 1 - slot)

        copies(s, i, slot, wait=True)
        k = kbuf[slot].reshape(cols, k_hbm.shape[-1])
        v = vbuf[slot].reshape(cols, v_hbm.shape[-1])
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if window:
            at = tok + (first_page(s) + i * bp) * pt    # a column's position
            live = own & (at <= pos) & (at > pos - window)
        else:
            live = jnp.logical_and(own, tok <= pos - i * (bp * pt))
        sc = jnp.where(live, sc, _NEG_INF)                   # [H, cols]
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(p, v,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, n_blk, block,
        (jnp.full((heads, 1), _NEG_INF, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, q.shape[-1]), jnp.float32)))
    slot_ref[0] = (slot0 + n_blk) % 2
    o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=('sm_scale', 'interpret', 'window', 'name'))
def paged_attention(q, k_pool, v_pool, table, positions, sm_scale,
                    interpret=False, window=0, name=None):
    """q [S, H, dh], pools [N, pt, KVH, dh], table [S, P] int32,
    positions [S] int32 -> [S, H, dh]: softmax over lane s's positions
    0..positions[s] (the last `window` of them where one is given) of
    sm_scale * q . k, times v, in fp32, query head h against K/V head
    h // (H / KVH). A table entry is read only below a lane's page count
    (and from its window's first page on); it must name a page of the
    pool (the caller clips)."""
    S, H, dh = q.shape
    N, pt, KVH = k_pool.shape[:3]
    if H % KVH:
        raise ValueError('%d query heads over %d K/V heads' % (H, KVH))
    P = table.shape[1]
    bp = block_pages(P, pt, KVH, dh, k_pool.dtype.itemsize)
    # a page as the [pt * KVH, dh] matrix it is in memory
    k3 = k_pool.reshape(N, pt * KVH, dh)
    v3 = v_pool.reshape(N, pt * KVH, dh)
    kernel = functools.partial(
        _kernel, sm_scale=float(sm_scale), pt=pt, heads=H, kv_heads=KVH,
        bp=bp, pages_per_slot=P, lanes=S,
        **({'window': int(window)} if window else {}))
    lane = pl.BlockSpec((None, H, dh), lambda s, *_: (s, 0, 0))
    buf = pltpu.VMEM((2, bp, pt * KVH, dh), k_pool.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[lane, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=lane,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # lanes run in order: the buffer parity and the block in flight
        # are carried from one to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=name or _NAMES[bool(window)],
    )(table.reshape(-1), positions, q, k3, v3)


# -- latent rows: one pool, values inside the keys ----------------------------

# pages a block of latent rows holds: a page of 16 rows of 640 floats is
# 40 KB, so 32 of them are 1.3 MB a block (2.6 MB of VMEM
# double-buffered) and a [H, 512] tile of scores a step
_LATENT_BLOCK_PAGES = 32


def latent_supported(page_tokens, row, value_dim):
    """A row is a whole number of lanes, as is the part of it the
    second product reads, and a page a whole number of sublane tiles."""
    return row % 128 == 0 and value_dim % 128 == 0 and page_tokens % 8 == 0


def _latent_kernel(table_ref, pos_ref, q_ref, pool_hbm, o_ref, buf, sems,
                   slot_ref, *, sm_scale, pt, bp, pages_per_slot, lanes,
                   value_dim):
    s = pl.program_id(0)
    heads, cols = q_ref.shape[0], bp * pt

    def n_pages(lane):
        return jnp.minimum(pos_ref[lane] // pt + 1, pages_per_slot)

    def copies(lane, blk, slot, wait=False):
        live = n_pages(lane)
        for j in range(bp):
            g = blk * bp + j

            @pl.when(g < live)
            def _():
                dma = pltpu.make_async_copy(
                    pool_hbm.at[table_ref[lane * pages_per_slot + g]],
                    buf.at[slot, j], sems.at[slot])
                dma.wait() if wait else dma.start()

    @pl.when(s == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        copies(0, 0, 0)

    slot0 = slot_ref[0]
    pos = pos_ref[s]
    n_blk = pl.cdiv(n_pages(s), bp)
    q = q_ref[...].astype(jnp.float32) * sm_scale           # [H, row]
    tok = jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 1)

    def block(i, carry):
        m, l, acc = carry
        slot = (slot0 + i) % 2

        @pl.when(i + 1 < n_blk)
        def _():
            copies(s, i + 1, 1 - slot)

        @pl.when(jnp.logical_and(i + 1 == n_blk, s + 1 < lanes))
        def _():
            copies(s + 1, 0, 1 - slot)

        copies(s, i, slot, wait=True)
        k = buf[slot].reshape(cols, q.shape[-1])
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sc = jnp.where(tok <= pos - i * cols, sc, _NEG_INF)  # [H, cols]
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(p, k[:, :value_dim],
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, n_blk, block,
        (jnp.full((heads, 1), _NEG_INF, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, value_dim), jnp.float32)))
    slot_ref[0] = (slot0 + n_blk) % 2
    o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=('sm_scale', 'value_dim', 'interpret'))
def paged_latent_attention(q, pool, table, positions, sm_scale, value_dim,
                           interpret=False):
    """q [S, H, row], pool [N, pt, row], table [S, P] int32, positions
    [S] int32 -> [S, H, value_dim]: softmax over lane s's positions
    0..positions[s] of sm_scale * q . row, times the row's first
    value_dim columns, in fp32. A table entry is read only below a
    lane's page count; it must name a page of the pool."""
    S, H, row = q.shape
    N, pt = pool.shape[:2]
    P = table.shape[1]
    bp = min(P, _LATENT_BLOCK_PAGES)
    kernel = functools.partial(
        _latent_kernel, sm_scale=float(sm_scale), pt=pt, bp=bp,
        pages_per_slot=P, lanes=S, value_dim=value_dim)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[pl.BlockSpec((None, H, row), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, H, value_dim),
                                   lambda s, *_: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, bp, pt, row), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='paged_latent_attention',
    )(table.reshape(-1), positions, q, pool)


# The kernel's name in the device's trace, by whether the caller gave a
# window; `paged_attention(name=)` is another caller's own (a block
# step's `paged_block_attention`, ops/attention_ops.py: its rows a lane
# ride as further query heads of their K/V head). Down here, and the
# function above kept to the lines it had: a Mosaic kernel's serialized
# text holds its source lines, and a line that moves is another
# compile-cache key for every cell's decode program.
_NAMES = ('paged_attention', 'paged_window_attention')


# -- heads of 64: two K/V heads a lane row ------------------------------------

def paged_attention_d64(q, k_pool, v_pool, table, positions, sm_scale,
                        interpret=False):
    """paged_attention for heads of 64 values, half a lane row: q
    [S, H, 64], pools [N, pt, KVH / 2, 128] (K/V heads 2m and 2m + 1 of a
    token side by side in row m: the bytes of [N, pt, KVH, 64] in the
    same order, which the TPU would otherwise lay out in rows of 128
    lanes half empty and relay out around every scatter), table [S, P],
    positions [S] -> [S, H, 64].

    The kernel above runs as it stands, on pairs: a query head is
    widened to a lane row with its 64 values in the half its K/V head
    occupies and zeros in the other, so q . row is q . k of its own head
    (the other head's lanes meet zeros); the H / (KVH / 2) query heads
    of a PAIR are rows of the one product against pages that are read
    once, as the H / KVH heads of one K/V head are at d 128; p . row
    gives both heads' values side by side and each query head keeps its
    half. The pool is read once and whole, no byte of it padded; the
    products carry 128 lanes where 64 count, on a unit that waits for
    the pages. Such a call carries the name `paged_attention_d64` in
    the device's trace."""
    S, H, dh = q.shape
    pairs, row = k_pool.shape[2:]
    if row != 2 * dh or H % (2 * pairs):
        raise ValueError('%d query heads of %d over %d rows of %d'
                         % (H, dh, pairs, row))
    # which half of its pair's row a query head's K/V head occupies
    upper = ((jnp.arange(H) // (H // (2 * pairs))) % 2 == 1)[None, :, None]
    zeros = jnp.zeros_like(q)
    wide = jnp.where(upper, jnp.concatenate([zeros, q], axis=-1),
                     jnp.concatenate([q, zeros], axis=-1))
    out = paged_attention(wide, k_pool, v_pool, table, positions,
                          sm_scale=sm_scale, interpret=interpret,
                          name='paged_attention_d64')
    return jnp.where(upper, out[..., dh:], out[..., :dh])
