"""A step's rows through the held experts they chose, in Pallas, for TPU:
the experts at least one row chose are read straight out of the held
stack, tile by tile, and the others are never touched (the op is
`moe_experts`, ops/moe_ops.py; `held_experts` / `held_gated_experts`
there are the same sum as one batched product over the whole stack, the
reference, the CPU's path and what a prefill chunk's rows take). A
step's rows are the engine's slots, one row a lane, or slots x B where a
lane's step is a block of B rows (`STEP_ROWS`).

lat [R, L] are the rows, w [R, held] a row's weight for each held
expert (0 where it did not choose it), W1 and W3 [held, L, F], W2
[held, F, L] the stack in HBM. `ids` [held] lists the touched experts
first, in rising order, its tail filled with the last of them, and `n`
[1] says how many there are (`touched_ids`); both are scalar-prefetched.

The grid is (held, F / tf), both in order. Step (j, f) of j < n reads
tile f of expert e = ids[j]: h = act(lat W1_e[:, tile]) * (lat
W3_e[:, tile]) (relu(.)^2 of the one product where there is no W3),
then acc += (h * w[:, e]) W2_e[tile, :], acc [R, L] in float32 in VMEM
for the whole walk. Every row goes through every expert that is read,
weighted by w exactly as in the batched product, and the walk is in
expert order: the sum is the product's with the zero terms left out.
The pipeline fetches the tile after the one being worked on while it is
multiplied. A step of j >= n names the block of the step before it (the
last touched expert's last tile), so nothing is fetched for it, and its
body is skipped; n = 0 gives zeros.

The contractions run at Mosaic's default precision for float32
operands, one bfloat16 pass with float32 accumulation, as XLA's batched
product does on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .latent_prefill import _traced_from_nowhere

__all__ = ['moe_experts', 'touched_ids', 'step_supported', 'tile_width',
           'STEP_ROWS']

# The most rows an op may have to take the kernel: a step program's rows
# are the engine's slots (32, 48, 64 in the benchmark's decode programs) or
# slots x a block's rows (32 x 4 where a lane's step is a block of 4: its
# masked rows share an embedding and route alike) and choose a quarter to
# nine tenths of a chip's held experts; a prefill chunk has 256, and 256
# rows x 8 / 320 is 6.4 pairs an expert (11 at 22 of 512), so over 99 % of
# the held experts are touched, there is nothing to skip, and the batched
# product reads the whole stack at 90 % of the HBM peak. One algorithm on
# two regimes that the op's static shape tells apart. Under it the bound
# is the kernel's memory (`step_supported`): the rows, the accumulator and
# the output are whole [rows, L] blocks beside the stack's tiles.
STEP_ROWS = 128

# what the kernel may ask of VMEM (a v5e core has 128 MiB), and the room
# left above `_vmem_bytes`' count for what the compiler lays out itself
_VMEM_CAP = 100 << 20
_VMEM_ROOM = 8 << 20

# bytes of the stack's tiles in flight (each matrix's tile, double-buffered):
# a tile of several MB amortises a grid step's fixed cost, and the rows'
# blocks, the accumulator and the products' temporaries have to fit beside
_TILE_BYTES = 48 << 20


def _relu(v):
    return jnp.maximum(v, 0.0)


def _relu2(v):
    return jnp.square(_relu(v))


def _silu(v):
    # jax.nn.silu is a jitted helper: its cached jaxpr would carry its
    # first caller's lines into the kernel's body (`_traced_from_nowhere`)
    return v * jax.lax.logistic(v)


_ACT = {'silu': _silu, 'relu': _relu, 'relu2': _relu2}


def tile_width(L, F, matrices, itemsize=4):
    """Columns of W1 (rows of W2) a grid step takes: the widest whole
    number of lane rows that divides F with every matrix's tile twice in
    `_TILE_BYTES`; one lane row where even that is more; 0 where F is no
    whole number of lane rows."""
    if F % 128:
        return 0
    fits = [tf for tf in range(128, F + 1, 128) if F % tf == 0
            and 2 * matrices * L * tf * itemsize <= _TILE_BYTES]
    return max(fits, default=128)


def step_supported(rows, L, F, held, matrices, itemsize=4):
    """Shapes the kernel takes: a step's rows (see `STEP_ROWS`), a whole
    number of sublanes of them, widths that are whole lane rows, and a
    walk of `held` experts of `matrices` matrices each whose blocks, at
    the tile the widths give (`_vmem_bytes`), fit under the limit the
    call sets."""
    if not (0 < rows <= STEP_ROWS and rows % 8 == 0 and L % 128 == 0
            and F % 128 == 0):
        return False
    tf = tile_width(L, F, matrices, itemsize)
    return _vmem_bytes(rows, L, tf, held, matrices, itemsize) \
        + _VMEM_ROOM <= _VMEM_CAP


def touched_ids(touched):
    """touched [held] bool -> (ids [held] int32, n [1] int32): the touched
    experts' indices first, in rising order, the tail filled with the
    last touched index (0 where none is), and how many are touched. By
    rank, not by a sort: entry j is the expert with j touched ones before
    it."""
    held = touched.shape[0]
    e = jnp.arange(held, dtype=jnp.int32)
    before = jnp.cumsum(touched.astype(jnp.int32)) - 1
    mine = touched[None, :] & (before[None, :] == e[:, None])
    ids = jnp.sum(jnp.where(mine, e[None, :], 0), axis=1)
    n = before[-1] + 1
    last = jnp.max(jnp.where(touched, e, 0))
    return jnp.where(e < n, ids, last).astype(jnp.int32), n.reshape(1)


def _kernel(ids_ref, n_ref, lat_ref, w_ref, *refs, act):
    # W1, W3 where the experts are gated, W2; then the output and scratch
    (w1_ref, *gate, w2_ref), (o_ref, acc) = refs[:-2], refs[-2:]
    j, f = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(j == 0, f == 0))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(j < n_ref[0])
    def _():
        lat = lat_ref[...]
        h = act(jnp.dot(lat, w1_ref[...], preferred_element_type=jnp.float32))
        for w3_ref in gate:
            h = h * jnp.dot(lat, w3_ref[...],
                            preferred_element_type=jnp.float32)
        # column ids[j] of w, picked by a mask: a lane cannot be indexed
        # by a value
        w = w_ref[...]
        col = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        mine = jax.lax.select(col == ids_ref[j], w, jnp.zeros_like(w))
        h = h * jnp.sum(mine, axis=1, keepdims=True)
        acc[...] += jnp.dot(h.astype(lat.dtype), w2_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(j == pl.num_programs(0) - 1,
                             f == pl.num_programs(1) - 1))
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _vmem_bytes(rows, L, tf, held, matrices, itemsize):
    """What the walk keeps in VMEM: each matrix's tile, the rows, their
    weights and the output block (all double-buffered by the pipeline),
    the accumulator, and the products with their temporaries (a tile
    rounded for the matrix unit beside the tile itself)."""
    lanes = -(-held // 128) * 128
    return (2 * matrices * L * tf * itemsize + matrices * L * tf * 2
            + 4 * rows * L * itemsize + 2 * rows * lanes * 4
            + 4 * rows * L + 4 * 4 * rows * tf)


@functools.partial(jax.jit, static_argnames=('act', 'tile', 'interpret'))
def moe_experts(lat, w, ids, n, w1, w3, w2, act='silu', tile=None,
                interpret=False):
    """lat [R, L], w [R, held] float32, ids [held] and n [1] int32
    (`touched_ids` of any(w != 0, axis=0)), W1 [held, L, F], W3 the same
    or None, W2 [held, F, L] -> sum over the experts ids[:n] of
    (act(lat W1_e) * (lat W3_e) * w[:, e]) W2_e, [R, L]; without W3 an
    expert is W2_e act(lat W1_e), `act` then 'relu2'. An expert outside
    ids[:n] is not read: its column of w must be zeros. `tile` (columns
    of W1 a grid step) defaults to what the shapes give."""
    R, L = lat.shape
    held, _, F = w1.shape
    matrices = 2 if w3 is None else 3
    tf = tile or tile_width(L, F, matrices, w1.dtype.itemsize)
    if not tf or F % tf or (tf % 128 and tf != F):
        raise ValueError('experts of %d x %d in tiles of %d' % (L, F, tf))
    nf = F // tf

    def tile_of(j, f, ids, n):
        # a step past the touched names the block of the step before it:
        # nothing is fetched for it
        return ids[j], jax.lax.select(j < n[0], f, jnp.int32(nf - 1))

    def up(j, f, ids, n):
        e, t = tile_of(j, f, ids, n)
        return e, 0, t

    def down(j, f, ids, n):
        e, t = tile_of(j, f, ids, n)
        return e, t, 0

    def whole(j, f, ids, n):
        return 0, 0

    stack = [pl.BlockSpec((None, L, tf), up) for _ in range(matrices - 1)] \
        + [pl.BlockSpec((None, tf, L), down)]
    need = _vmem_bytes(R, L, tf, held, matrices, w1.dtype.itemsize)
    call = pl.pallas_call(
        functools.partial(_kernel, act=_ACT[act]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(held, nf),
            in_specs=[pl.BlockSpec((R, L), whole),
                      pl.BlockSpec((R, held), whole)] + stack,
            out_specs=pl.BlockSpec((R, L), whole),
            scratch_shapes=[pltpu.VMEM((R, L), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, L), lat.dtype),
        # the accumulator is carried from step to step
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=min(_VMEM_CAP,
                                 max(32 << 20, need + _VMEM_ROOM))),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='moe_experts')
    mats = (w1, w2) if w3 is None else (w1, w3, w2)
    with _traced_from_nowhere():
        return call(ids, n, lat, w.astype(jnp.float32), *mats)
