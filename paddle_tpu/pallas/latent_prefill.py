"""A prefill chunk's attention over a paged pool of latent rows, in
Pallas, for TPU: the walk of `paged_attention._latent_kernel` with a
block of query rows in place of one lane's heads (the op is
`paged_latent_prefill`, ops/latent_attention_ops.py; `prefill_absorbed`
there is the same sum folded in plain XLA, the reference and the CPU's
path).

q [C, H, row] is the chunk's absorbed, scaled query (multi-head latent
attention in its absorbed form: a token's H heads are H rows of one
product over a block of latent rows), pool [N, pt, row] the layer's
pages in HBM, table [P] the stream's page table, `first` the position
of the chunk's first row (row i stands at first + i) and `length` how
many of its rows are live; the rest of a chunk is padding.

The grid is one step per tile of `tq` chunk tokens, tq * H query rows.
A tile walks the stream's pages 0 .. (first + its last live row) // pt
in blocks of `bp` pages, each page one contiguous DMA into a
double-buffered VMEM block; the block after the one being worked on is
always in flight, across tiles too (a tile's last block starts the next
live tile's first). For a block: scores [tq H, bp pt] = q . block^T over
the whole row, masked by row (token <= the row's own position: the rows
of a tile differ in position), an online softmax in fp32 (m, l, acc
[tq H, value_dim]) and the second product over the block's first
`value_dim` columns. The scores never leave VMEM. A tile whose first
token is at or past `length` copies nothing, multiplies nothing and
writes zeros; the tile that straddles `length` is computed whole and its
dead rows zeroed on the way out, so rows >= length are zeros.

The contractions run at Mosaic's default precision for float32
operands, one bfloat16 pass, as in the decode kernel and in
`prefill_absorbed`'s `jnp.dot`.

A masked column contributes exp(-1e30 - m) = 0.0 times whatever the
buffer holds there, so the buffer is zeroed once: a page slot that was
never copied into must not hold a NaN.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import latent_supported

__all__ = ['paged_latent_prefill', 'prefill_supported', 'tile_tokens']

_NEG_INF = -1e30
# query rows a tile holds: 16 tokens x 64 heads. A block of pages is
# converted and pushed through the matrix unit once a tile, so a taller
# tile amortises it; at 1024 rows of 640 the query is 2.6 MB, acc 2.0,
# the scores and their exponentials 2.0 each at 512 columns a block
_TILE_ROWS = 1024
# pages a block holds, as the decode kernel's: 32 pages of 16 rows of 640
# floats are 1.3 MB, 2.6 MB of VMEM double-buffered
_BLOCK_PAGES = 32


def tile_tokens(chunk, heads):
    """Chunk tokens a tile: the most that divide the chunk within
    `_TILE_ROWS` query rows, a whole number of sublane tiles; 0 where
    there is none."""
    for tq in range(min(chunk, max(1, _TILE_ROWS // heads)), 0, -1):
        if chunk % tq == 0 and (tq * heads) % 8 == 0:
            return tq
    return 0


def prefill_supported(chunk, heads, page_tokens, row, value_dim):
    """Shapes the kernel tiles: the decode kernel's pages, and a chunk
    that is a whole number of tiles."""
    return latent_supported(page_tokens, row, value_dim) \
        and tile_tokens(chunk, heads) > 0


def _div(x, by):
    """x // by for an x that is not negative, as the one primitive:
    `//`, `%` and `jnp.where` on a traced value are jitted helpers whose
    jaxpr, and with it the source lines of whoever traced them first in
    the process, is cached and would land in this kernel's body (see
    `_traced_from_nowhere`); the kernel takes lax.div, lax.rem and
    lax.select."""
    return jax.lax.div(x, jnp.int32(by))


def _kernel(table_ref, meta_ref, q_ref, pool_hbm, o_ref, buf, sems, slot_ref,
            *, pt, bp, pages, heads, tq, value_dim):
    t = pl.program_id(0)
    first, length = meta_ref[0], meta_ref[1]
    rows, cols = q_ref.shape[0], bp * pt

    def live(tile):
        return tile * tq < length

    def n_pages(tile):
        last = first + jnp.minimum((tile + 1) * tq, length) - 1
        return jnp.minimum(_div(last, pt) + 1, pages)

    def copies(tile, blk, slot, wait=False):
        """Start (or wait for) the copies of block `blk` of `tile`'s
        walk, its live pages only, into buffer `slot`."""
        n = n_pages(tile)
        for j in range(bp):
            g = blk * bp + j

            @pl.when(g < n)
            def _():
                dma = pltpu.make_async_copy(
                    pool_hbm.at[table_ref[g]], buf.at[slot, j],
                    sems.at[slot])
                dma.wait() if wait else dma.start()

    @pl.when(t == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0

        @pl.when(live(0))
        def _():
            copies(0, 0, 0)

    @pl.when(jnp.logical_not(live(t)))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live(t))
    def _():
        slot0 = slot_ref[0]
        n_blk = _div(n_pages(t) + (bp - 1), bp)
        q = q_ref[...]                                   # [tq H, row]
        # a row's token of the chunk, and its position in the stream
        token = t * tq + _div(jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0), heads)
        pos = first + token

        def block(i, carry):
            m, l, acc = carry
            slot = jax.lax.rem(slot0 + i, 2)

            @pl.when(i + 1 < n_blk)
            def _():
                copies(t, i + 1, 1 - slot)

            @pl.when(jnp.logical_and(i + 1 == n_blk, live(t + 1)))
            def _():
                copies(t + 1, 0, 1 - slot)

            copies(t, i, slot, wait=True)
            k = buf[slot].reshape(cols, q.shape[-1])
            sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
            sc = jax.lax.select(col <= pos - i * cols, sc,
                                jnp.full_like(sc, _NEG_INF))
            m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            acc = alpha * acc + jnp.dot(p, k[:, :value_dim],
                                        preferred_element_type=jnp.float32)
            return m_new, l, acc

        m, l, acc = jax.lax.fori_loop(
            0, n_blk, block,
            (jnp.full((rows, 1), _NEG_INF, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, value_dim), jnp.float32)))
        slot_ref[0] = jax.lax.rem(slot0 + n_blk, 2)
        inv = jax.lax.select(token < length, 1.0 / l, jnp.zeros_like(l))
        o_ref[...] = (acc * inv).astype(o_ref.dtype)


@functools.cache
def _pool_thread_traceback():
    from concurrent.futures import ThreadPoolExecutor
    from jax._src.lib import xla_client
    with ThreadPoolExecutor(1) as pool:
        return pool.submit(xla_client.Traceback.get_traceback).result()


def _traced_from_nowhere():
    """A scope in which every op traced carries one fixed traceback,
    taken on a thread of the standard library's pool: it holds no frame
    of this checkout. A Mosaic kernel's serialized body is part of its
    executable's cache key and holds the source lines of its ops and of
    the frames that called them (ROADMAP S17); traced in this scope the
    body is the same from every call site and in every checkout, and a
    moved line in the emitter, the executor or the predictor no longer
    compiles the prefill program anew. jax has no public scope for this
    (`jax_traceback_in_locations_limit` is process-wide and read when a
    module is lowered, not when it is traced); where its private one is
    gone the ops keep the tracebacks they have."""
    try:
        from jax._src import source_info_util
        return source_info_util.user_context(_pool_thread_traceback())
    except Exception:
        return contextlib.nullcontext()


def _vmem_bytes(rows, row, cols, value_dim, itemsize):
    """What a tile keeps in VMEM: the query and the output block (both
    double-buffered by the pipeline), the page buffer, acc, and the
    scores, their mask and their exponentials; m, l and alpha take a
    whole lane row a query row."""
    return (2 * rows * row * itemsize + 2 * rows * value_dim * itemsize
            + 2 * cols * row * itemsize + 4 * rows * value_dim
            + 3 * 4 * rows * cols + 6 * 4 * rows * 128)


@functools.partial(jax.jit, static_argnames=('value_dim', 'tile',
                                             'block_pages', 'interpret'))
def paged_latent_prefill(q, pool, table, first, length, value_dim,
                         tile=None, block_pages=None, interpret=False):
    """q [C, H, row] (absorbed and scaled), pool [N, pt, row], table [P]
    int32, first and length int32 scalars -> [C, H, value_dim]: for row
    i < length, softmax over positions 0..first + i of q . row, times
    the row's first value_dim columns, in fp32; zeros for the rows from
    `length` on. A table entry is read only below the page of the last
    live row; it must name a page of the pool. `tile` (chunk tokens a
    grid step) and `block_pages` default to what the shapes give."""
    C, H, row = q.shape
    pt = pool.shape[1]
    P = table.shape[0]
    tq = tile or tile_tokens(C, H)
    if not tq or C % tq or (tq * H) % 8:
        raise ValueError('a chunk of %d tokens x %d heads in tiles of %d'
                         % (C, H, tq))
    bp = min(P, block_pages or _BLOCK_PAGES)
    rows = tq * H
    kernel = functools.partial(_kernel, pt=pt, bp=bp, pages=P, heads=H,
                               tq=tq, value_dim=value_dim)

    def tile_block(t, table, meta):
        # a dead tile names the last live tile's block: nothing is
        # fetched for it
        return jnp.minimum(t, _div(jnp.maximum(meta[1] - 1, 0), tq)), 0

    need = _vmem_bytes(rows, row, bp * pt, value_dim, q.dtype.itemsize)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(C // tq,),
            in_specs=[pl.BlockSpec((rows, row), tile_block),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((rows, value_dim),
                                   lambda t, *_: (t, 0)),
            scratch_shapes=[pltpu.VMEM((2, bp, pt, row), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((C * H, value_dim), q.dtype),
        # tiles run in order: the buffer parity and the block in flight
        # are carried from one to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=min(96 << 20, max(32 << 20, 2 * need))),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='paged_latent_prefill')
    meta = jnp.stack([first, jnp.minimum(length, C)]).astype(jnp.int32)
    with _traced_from_nowhere():
        out = call(table, meta, q.reshape(C * H, row), pool)
    return out.reshape(C, H, value_dim)
